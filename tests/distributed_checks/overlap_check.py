"""Multi-device (8 fake CPU devices) validation of the overlapped bucket
sync (BucketSpec.overlap → repro.train.bucketing.overlap_params).  Run by
tests/test_overlap.py in a subprocess:

    python overlap_check.py

Checks (ISSUE 5 acceptance):
  * schedule independence: overlapped grads == post-backward grads
    bit-for-bit for every tested preset — stateless psum (fixed_k_1bit),
    stateless gather (bernoulli_seed_1bit), packed plane (binary_packed)
    and the stateful DRIVE stack (ef_rotated_binary), whose per-bucket EF
    residuals must also match bit-for-bit across 3 chained steps even
    though buckets complete out of backward order;
  * HLO: the expected collective launches per bucket (counted in the
    module the program emits, before XLA's combiner — 1 for psum/exact buckets, 2 for flat-scatter buckets whose
    decode re-gathers the decoded shards, DESIGN.md §13), and at the
    dependency level the per-bucket collectives *interleave* with
    backward — the first-ready bucket's collective is independent of the
    trailing backward dots (neither ancestor nor descendant), so it can be
    issued before the final backward op instead of after the loss graph;
  * the real train step (build_train_step, smoke model, EF shared_support)
    takes bit-identical steps with overlap ON and OFF.
Exits non-zero on failure.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# the shared post-vs-overlapped step construction (same module the
# bench_bucketing overlap sweep imports, so check and bench agree).
import overlap_harness as oh  # noqa: E402

from repro.core import types  # noqa: E402
from repro.train import bucketing  # noqa: E402

N = 8
L, M = 6, 64           # 6-layer MLP chain: w_[i] (M,M) + b_[i] (M,)
STEPS = 3              # chained EF steps (state threads across rounds)

mesh = jax.make_mesh((N,), ("data",))
MESH_AXES = ("data",)
MSIZES = {"data": N}

SHAPES, SPECS = oh.build_tree(L, M)
PARAMS = oh.init_params(SHAPES)
X = jax.random.normal(jax.random.PRNGKey(1), (N * 4, M))


def check(name, ok, detail=""):
    print(f"[{'ok' if ok else 'FAIL'}] {name} {detail}")
    if not ok:
        raise SystemExit(f"FAILED: {name} {detail}")


def make_steps(cfg, plan):
    """(ref_fn, ovl_fn): one sync'd-grad round -> (grads, new_ef)."""
    return oh.make_sync_steps(mesh, L, cfg, plan)


# --------------------------------------------------------------------------- #
# Schedule independence: overlapped == post-backward, bit-for-bit.
# --------------------------------------------------------------------------- #

# every registered preset (the docstring's "every registered codec" claim
# is enforced, not sampled) + the exact baseline.
from repro.configs.registry import COMPRESSION_PRESETS  # noqa: E402

PRESETS = ["none"] + sorted(COMPRESSION_PRESETS)

for preset in PRESETS:
    cfg = oh.mkcfg(preset, M)
    use_ef = cfg.error_feedback
    plan = bucketing.build_plan(SHAPES, SPECS, MESH_AXES, MSIZES, cfg)
    ref, ovl = make_steps(cfg, plan)
    ef_r = ef_o = bucketing.init_ef_state(plan, cfg) if use_ef else {}
    g_r = g_o = None
    for stp in range(STEPS if use_ef else 1):
        key = jax.random.fold_in(jax.random.PRNGKey(7), stp)
        g_r, ef_r = ref(PARAMS, ef_r, X, key)
        g_o, ef_o = ovl(PARAMS, ef_o, X, key)
    ok_g = all(np.array_equal(np.asarray(g_r[n]), np.asarray(g_o[n]))
               for n in SHAPES)
    check(f"{preset}.grads_bit_identical", ok_g)
    if use_ef:
        ok_e = all(np.array_equal(np.asarray(ef_r[b]), np.asarray(ef_o[b]))
                   for b in ef_r)
        check(f"{preset}.ef_bit_identical_{STEPS}steps", ok_e,
              f"({len(ef_r)} bucket residuals)")


# --------------------------------------------------------------------------- #
# HLO: per-bucket launches + dependency-level interleaving with backward.
# --------------------------------------------------------------------------- #

def parse_computations(hlo: str):
    """{computation name: [(instr, op, [operand instrs])]} from HLO text."""
    comps = {}
    cur = None
    for line in hlo.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w\.\-]+)\s.*\{$", line.strip())
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        mi = re.match(
            r"\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(?:\([^=]*\)|\S+)\s+"
            r"([\w\-]+)\((.*)$",
            line)
        if not mi:
            continue
        name, op, rest = mi.groups()
        # operands: everything inside the op's first paren group
        depth, args = 1, ""
        for ch in rest:
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
            args += ch
        operands = re.findall(r"%?([\w\.\-]+)", args)
        comps[cur].append((name, op, operands))
    return comps


def interleave_stats(ovl, ef0):
    """(collectives, dots, {collective: #dots independent of it}).

    The first collective in emission order belongs to the *last-applied*
    sync point — the earliest-ready bucket (transpose order reverses the
    forward tag order; the earliest-ready bucket holds the highest-sorted
    leaves, tagged last).
    """
    hlo = ovl.lower(PARAMS, ef0, X, jax.random.PRNGKey(7)).as_text(
        dialect="hlo")
    comps = parse_computations(hlo)
    # the computation holding the inlined shard_map body (dots + colls)
    body = None
    for name, instrs in comps.items():
        ops = {op for _, op, _ in instrs}
        if ("dot" in ops) and ops & {"all-gather", "all-reduce"}:
            body = instrs
            break
    assert body is not None, "no computation with both dots and collectives"
    defs = {name: set(operands) for name, _, operands in body}
    known = set(defs)

    anc_cache = {}

    def ancestors(name):
        if name in anc_cache:
            return anc_cache[name]
        anc_cache[name] = set()          # cycle-safe (HLO is a DAG)
        out = set()
        for o in defs.get(name, ()):
            if o in known:
                out.add(o)
                out |= ancestors(o)
        anc_cache[name] = out
        return out

    colls = [name for name, op, _ in body
             if op in ("all-gather", "all-reduce")]
    dots = [name for name, op, _ in body if op == "dot"]
    indep = {}
    for c in colls:
        anc_c = ancestors(c)
        n = sum(1 for d in dots
                if d not in anc_c and c not in ancestors(d))
        indep[c] = n
    return colls, dots, indep


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# Extra collectives per *compressed* bucket beyond the one wire gather /
# psum: ef_rotated_binary flat-scatters (§13) so each compressed bucket
# re-gathers its decoded shard — one extra all-gather (the binary family
# needs no counts exchange).  fixed_k_1bit is a single psum.
EXTRA_COLLS = {"fixed_k_1bit": 0, "ef_rotated_binary": 1}

for preset in ["fixed_k_1bit", "ef_rotated_binary"]:
    cfg = oh.mkcfg(preset, M)
    plan = bucketing.build_plan(SHAPES, SPECS, MESH_AXES, MSIZES, cfg)
    use_ef = cfg.error_feedback
    n_expect = sum(1 + (EXTRA_COLLS[preset] if b.kind == "compressed" else 0)
                   for b in plan.buckets)

    # the expected collective launches per bucket in the module the program
    # emits, before XLA's passes: the CPU backend's all-reduce combiner
    # merges independent all-reduces afterwards, so the compiled count
    # says what that pass did, not what the sync schedule issues.
    _, ovl = make_steps(cfg, plan)
    ef0 = bucketing.init_ef_state(plan, cfg) if use_ef else {}
    emitted = ovl.lower(PARAMS, ef0, X,
                        jax.random.PRNGKey(7)).as_text(dialect="hlo")
    n_launch = sum(1 for instrs in parse_computations(emitted).values()
                   for _, op, _ in instrs if op in COLLECTIVES)
    check(f"{preset}.launch_per_bucket", n_launch == n_expect,
          f"launches={n_launch} expected={n_expect} "
          f"buckets={len(plan.buckets)}")

    colls, dots, indep = interleave_stats(ovl, ef0)
    check(f"{preset}.coll_count", len(colls) == n_expect,
          f"{len(colls)} collectives for {len(plan.buckets)} buckets"
          f" (expected {n_expect})")
    # Interleaved, not trailing: the first-issued (earliest-ready) bucket's
    # collective is independent of part of backward — it does not wait for
    # the final backward op the way a post-loss-graph sync stage would
    # force once grads are materialized as a unit.  The earliest-ready
    # bucket holds the *last* layers' weights, whose cotangents exist
    # before any earlier layer's backward dot runs.
    first = colls[0]
    check(f"{preset}.interleaves_backward", indep[first] >= 2,
          f"first collective independent of {indep[first]}/{len(dots)} dots"
          f" (per-bucket: {[indep[c] for c in colls]})")

# --------------------------------------------------------------------------- #
# The real train step: overlap ON == OFF, bit-for-bit (params + EF state).
# --------------------------------------------------------------------------- #

from repro.configs.base import RunConfig, ShapeSpec  # noqa: E402
from repro.configs.registry import smoke_config  # noqa: E402
from repro.train import train_step as ts  # noqa: E402

cfg_a = smoke_config("qwen3-4b")
shape = ShapeSpec("cli", "train", 64, 8)
comp = types.CompressionConfig(
    encoder=types.EncoderSpec(kind="fixed_k", fraction=1 / 16),
    mode="shared_support", axes=("data",), min_compress_size=1024,
    error_feedback=True)
batch = {"tokens": jnp.zeros((8, 64), jnp.int32) + 3,
         "labels": jnp.ones((8, 64), jnp.int32),
         "mask": jnp.ones((8, 64), jnp.float32)}
tmesh = jax.make_mesh((4, 2), ("data", "model"))
outs = {}
for overlap in (True, False):
    run = RunConfig(
        microbatches=1, model_parallel=True, seq_shard=True,
        attn_chunk_q=64, attn_chunk_k=64, remat=False,
        compression=dataclasses.replace(
            comp, bucket=types.BucketSpec(overlap=overlap)))
    step_fn, init_fn, _, _, _ = ts.build_train_step(tmesh, cfg_a, run, shape)
    params, opt, ef = init_fn(jax.random.PRNGKey(0))
    for stp in range(2):
        params, opt, ef, metrics = step_fn(params, opt, ef, batch,
                                           jnp.int32(stp))
    outs[overlap] = (jax.tree.map(np.asarray, params),
                     jax.tree.map(np.asarray, ef))

p_on, ef_on = outs[True]
p_off, ef_off = outs[False]
check("train_step.params_bit_identical",
      all(np.array_equal(p_on[k], p_off[k]) for k in p_on))
check("train_step.ef_bit_identical",
      set(ef_on) == set(ef_off)
      and all(np.array_equal(ef_on[k], ef_off[k]) for k in ef_on),
      f"({len(ef_on)} bucket residuals)")

print("ALL OVERLAP CHECKS PASSED")
