"""Wire bytes + step time of the compressed-mean collective per registry
preset, measured from lowered HLO and timed execution on an 8-device mesh
(subprocess: device count is locked at first jax init, and benchmarks must
see 1 device by default).

The preset sweep comes from repro.configs.registry.COMPRESSION_PRESETS —
i.e. the same codec registry the production dispatch consults — plus three
reference points ("none" exact, "fixed_k_gather", "binary_dense" dense
simulation).  Two byte conventions are reported per preset:

* ``wire_bytes`` — ring-adjusted per-device wire traffic (hlo_cost's
  roofline convention: all-reduce pays 2·b·(s−1)/s, all-gather b·(s−1)/s);
* ``payload_bytes`` — the star-protocol payload Σ_i |message_i| that the
  paper's C sums charge (all-gather: the gathered result size; all-reduce:
  n × the reduced buffer).  Every preset's payload must equal the resolved
  codec's ``wire_bits + scatter_bits`` accounting exactly (scatter_bits is
  nonzero only for the §12 flat-scatter presets: the i32 rank-offset counts
  plus the decoded f32 shard gather), binary must undercut the dense
  f32 simulation ≥ 8× (it lands at ~32×), the §7.2 rotated presets must
  cost exactly their un-rotated codec's payload (seed-only overhead), and
  the error-feedback presets must cost exactly their EF-free codec's
  payload byte-for-byte (residuals are local — repro.core.wire.ef), with
  ``ternary_opt`` equal to ``ternary_packed`` (the §6 split rides the
  plane).

The ``robust`` section times the decode-policy hook (DESIGN.md §14):
trim(1)/trim(2) vs plain-mean decode µs for every gather preset at the
same d/n — the wire is policy-blind, so the delta is pure
order-statistics cost on the gathered stack.

:func:`collect` is the machine-readable entry point benchmarks/run.py uses
to emit BENCH_collectives.json.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

_INNER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, functools, json, re, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import registry as cfg_registry
from repro.core import collectives, types, wire
from repro.launch import hlo_cost

mesh = jax.make_mesh((8,), ("data",))
N = 8
D = int(os.environ.get("BENCH_D", 1 << 20))
REPS = int(os.environ.get("BENCH_REPS", 3))

def preset_cfgs():
    out = {"none": types.CompressionConfig(mode="none")}
    for name in sorted(cfg_registry.COMPRESSION_PRESETS):
        out[name] = cfg_registry.compression_preset(name, axes=("data",))
    # reference points: the fixed-k star path and the dense simulation.
    out["fixed_k_gather"] = dataclasses.replace(
        out["fixed_k_1bit"], mode="gather_decode")
    out["binary_dense"] = dataclasses.replace(
        out["binary_packed"], mode="dense_sim", scatter_decode=False)
    # f32 wire for the sweep: the CPU backend lowers bf16 collectives at
    # f32 (the measured bytes would be 2x the bf16 accounting), so the
    # payload==accounting equality is only byte-exact at f32 — same
    # normalization as tests/distributed_checks/*.  TPU keeps bf16 native;
    # the shipped presets themselves stay bf16.
    return {k: dataclasses.replace(v, min_compress_size=0,
                                   wire_dtype="float32")
            for k, v in out.items()}

res = {"schema": 1, "n": N, "d": D, "reps": REPS, "wire_dtype": "float32",
       "presets": {}}
xs = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.float32) * 0.3
key = jax.random.PRNGKey(1)
for name, cfg in preset_cfgs().items():
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P(), check_vma=False)
    def f(x, k):
        return collectives.compressed_mean(x.reshape(D), k, cfg)
    fj = jax.jit(f)
    comp = fj.lower(jax.ShapeDtypeStruct((N, D), jnp.float32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    txt = comp.as_text()
    hc = hlo_cost.analyze_text(txt)
    # star payload Σ_i |message_i|, read straight off the collective result
    # shapes (all-gather: the gathered result; all-reduce: N × the reduced
    # buffer).  Deliberately NOT via hlo_cost's ring bytes: those apply the
    # TPU-normalization heuristics of DESIGN.md §6 (large f32 gathers are
    # assumed to be CPU-legalized bf16 and charged half), which would
    # misprice this sweep's genuine f32 wire buffers.
    nbytes = {"f32": 4, "u32": 4, "s32": 4, "bf16": 2}
    payload = 0.0
    for dt, dims, op in re.findall(
            r"= (f32|u32|s32|bf16)\[([\d,]+)\]\S* (all-gather|all-reduce)"
            r"(?:-start)?\(", txt):
        b = nbytes[dt]
        for x in dims.split(","):
            b *= int(x)
        payload += b * (N if op == "all-reduce" else 1)
    fj(xs, key).block_until_ready()  # warm: compile + first-touch allocs
    fj(xs, key).block_until_ready()  # settle — same discipline as the
    # overlap + device_step sections, so µs are comparable in kind.  NOTE
    # step_time_us is still 8 virtual devices serialized on one core at
    # BENCH_D with a free in-memory wire: absolute µs are NOT comparable
    # to the overlap section (a whole L-layer MLP step at a much smaller
    # total grad dim) — bench_device_step models the per-device step.
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fj(xs, key)
    out.block_until_ready()
    step_us = (time.perf_counter() - t0) / REPS * 1e6
    entry = {"wire_bytes": hc.coll_wire_bytes, "payload_bytes": payload,
             "step_time_us": step_us,
             "ops": {k: round(v) for k, v in hc.coll_exec.items()}}
    if cfg.mode != "none":
        codec = wire.resolve(cfg)
        entry["codec"] = codec.name
        entry["reduce"] = codec.reduce
        # flat-scatter presets (§12) ship two extra collectives — the
        # i32 rank-offset counts and the decoded f32 shard gather —
        # billed by scatter_bits; hier/non-scatter presets add 0.
        entry["accounted_payload_bytes"] = (
            codec.wire_bits(N, D, cfg) + codec.scatter_bits(N, D, cfg)) / 8
        # recorded separately so the cross-preset equalities (rotation is
        # seed-only, EF rides the inner format) can compare wire payloads
        # net of the scatter-decode gathers.
        entry["scatter_payload_bytes"] = codec.scatter_bits(N, D, cfg) / 8
    res["presets"][name] = entry

# robust decode overhead: trimmed vs mean decode us per gather preset at
# the same d/n (f = 0 is the mean round already timed above; trim(f) only
# changes the DECODE reduction — the wire is policy-blind, so any delta is
# pure order-statistics cost on the gathered stack).
res["robust"] = {}
for name, cfg in preset_cfgs().items():
    if cfg.mode == "none":
        continue
    if wire.resolve(cfg).reduce != "all_gather":
        continue  # psum codecs reject robust policies (no per-peer rows)
    entry = {"mean_us": res["presets"][name]["step_time_us"]}
    for f_, tag in ((1, "trim1_us"), (2, "trim2_us")):
        rcfg = dataclasses.replace(cfg, decode_policy=f"trim({f_})")
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P("data"), P()), out_specs=P(),
                           check_vma=False)
        def f(x, k, rcfg=rcfg):
            return collectives.compressed_mean(x.reshape(D), k, rcfg)
        fj = jax.jit(f)
        fj(xs, key).block_until_ready()
        fj(xs, key).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = fj(xs, key)
        out.block_until_ready()
        entry[tag] = (time.perf_counter() - t0) / REPS * 1e6
    entry["trim_overhead_x"] = entry["trim1_us"] / max(entry["mean_us"],
                                                       1e-9)
    res["robust"][name] = entry
print(json.dumps(res))
"""


# One forced-device-count process per simulated node count: flat vs
# hierarchical cross-host traffic + wall-clock for the linear gather codecs
# (docs/DESIGN.md §11).  Cross-host = any collective whose replica group
# spans two inner blocks (device linear id = pod*n_in + data).
_NODE_INNER = r"""
import os
N = int(os.environ["BENCH_N"])
N_IN = int(os.environ.get("BENCH_N_IN", 2))
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N}"
import dataclasses, functools, json, re, time
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs import registry as cfg_registry
from repro.core import collectives, wire

D = int(os.environ.get("BENCH_D", 1 << 18))
REPS = int(os.environ.get("BENCH_REPS", 3))
mesh = Mesh(np.array(jax.devices()).reshape(N // N_IN, N_IN),
            ("pod", "data"))
MSIZES = {"pod": N // N_IN, "data": N_IN}

def cross_host_bytes(txt):
    nbytes = {"f32": 4, "u32": 4, "bf16": 2}
    total = 0.0
    for line in txt.splitlines():
        m = re.search(r"= (f32|u32|bf16)\[([\d,]*)\]\S* "
                      r"(all-gather|all-reduce)(?:-start)?\(", line)
        if not m:
            continue
        g = re.search(r"replica_groups=\{\{([\d,{} ]*)\}\}", line)
        if not g:
            continue
        groups = [[int(v) for v in grp.split(",") if v.strip()]
                  for grp in g.group(1).split("},{")]
        if not any(len({i // N_IN for i in grp}) > 1 for grp in groups):
            continue
        b = nbytes[m.group(1)]
        for v in m.group(2).split(","):
            if v:
                b *= int(v)
        total += b * (N if m.group(3) == "all-reduce" else 1)
    return total

def bench(cfg):
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(("pod", "data")), P()), out_specs=P(),
                       check_vma=False)
    def f(x, k):
        return collectives.compressed_mean(x.reshape(D), k, cfg)
    fj = jax.jit(f)
    comp = fj.lower(jax.ShapeDtypeStruct((N, D), jnp.float32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    xs = jax.random.normal(jax.random.PRNGKey(0), (N, D), jnp.float32) * 0.3
    key = jax.random.PRNGKey(1)
    fj(xs, key).block_until_ready()
    fj(xs, key).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fj(xs, key)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / REPS * 1e6
    return us, cross_host_bytes(comp.as_text())

res = {"n": N, "n_in": N_IN, "d": D, "reps": REPS, "codecs": {}}
for name in ("bernoulli", "fixed_k"):
    hier = dataclasses.replace(
        cfg_registry.compression_preset("hier_" + name),
        wire_dtype="float32", min_compress_size=0)
    flat = dataclasses.replace(hier, axes=("pod", "data"), inner_axes=(),
                               scatter_decode=False)
    flat_us, flat_cross = bench(flat)
    hier_us, hier_cross = bench(hier)
    n_eff = wire.effective_nodes(hier, N, MSIZES)
    res["codecs"][name] = {
        "flat_us": flat_us, "hier_us": hier_us,
        "flat_payload_bytes": flat_cross,
        "hier_cross_bytes": hier_cross,
        "accounted_cross_bytes":
            wire.resolve(hier).wire_bits(n_eff, D, hier) / 8,
    }
print(json.dumps(res))
"""


_CACHE: dict = {}


def collect(d: int | None = None, reps: int = 3, timeout: int = 900) -> dict:
    """Run the 8-device sweep in a subprocess; returns the JSON payload.

    Memoized per (d, reps) so run.py's CSV rows and JSON record share one
    sweep.
    """
    if (d, reps) in _CACHE:
        return _CACHE[(d, reps)]
    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    if d is not None:
        env["BENCH_D"] = str(d)
    env["BENCH_REPS"] = str(reps)
    proc = subprocess.run([sys.executable, "-c", _INNER], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"bench_collectives subprocess failed:\n"
                           f"{proc.stderr[-2000:]}")
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    _CACHE[(d, reps)] = res
    return res


def collect_node_sweep(ns: tuple = (4, 8, 16), d: int = 1 << 18,
                       reps: int = 3, timeout: int = 900) -> dict:
    """Flat vs hierarchical collectives across simulated node counts.

    One subprocess per n (the fake-device count is locked at jax init), a
    (n/2, 2)-mesh each; returns ``{str(n): record}`` for the JSON
    ``node_sweep`` section.  Memoized per (ns, d, reps) like collect().
    """
    ck = ("node_sweep", tuple(ns), d, reps)
    if ck in _CACHE:
        return _CACHE[ck]
    root = pathlib.Path(__file__).resolve().parent.parent
    out = {}
    for n in ns:
        env = dict(os.environ)
        env["PYTHONPATH"] = str(root / "src")
        env.pop("XLA_FLAGS", None)
        env["BENCH_N"] = str(n)
        env["BENCH_D"] = str(d)
        env["BENCH_REPS"] = str(reps)
        proc = subprocess.run([sys.executable, "-c", _NODE_INNER], env=env,
                              capture_output=True, text=True,
                              timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(f"node_sweep subprocess (n={n}) failed:\n"
                               f"{proc.stderr[-2000:]}")
        out[str(n)] = json.loads(proc.stdout.strip().splitlines()[-1])
    _CACHE[ck] = out
    return out


def check_node_scaling(sweep: dict) -> list:
    """Node-sweep invariants (must be empty):

    * the hierarchy's cross-host bytes equal the effective-n accounting
      exactly and shrink by the inner-group factor vs flat, at every n;
    * at the largest simulated n, the reduce-scatter decode beats the flat
      gather decode wall-clock for bernoulli — the codec whose decode
      regenerates n·d support draws when flat but only n_eff·(d/n_in) when
      sharded, so decode FLOPs dominate and the O(n·d) → O(d) win shows up
      even on fake single-core meshes.
    """
    bad = []
    for n, rec in sweep.items():
        for name, e in rec["codecs"].items():
            if e["hier_cross_bytes"] != e["accounted_cross_bytes"]:
                bad.append(f"node_sweep n={n} {name}: cross bytes "
                           f"{e['hier_cross_bytes']:.0f} != accounted "
                           f"{e['accounted_cross_bytes']:.0f}")
            if e["flat_payload_bytes"] != rec["n_in"] * e["hier_cross_bytes"]:
                bad.append(f"node_sweep n={n} {name}: flat payload "
                           f"{e['flat_payload_bytes']:.0f} != n_in x hier "
                           f"{rec['n_in'] * e['hier_cross_bytes']:.0f}")
    top = max(sweep, key=int)
    e = sweep[top]["codecs"]["bernoulli"]
    if not e["hier_us"] < e["flat_us"]:
        bad.append(f"node_sweep n={top} bernoulli: hier {e['hier_us']:.0f}us "
                   f"not faster than flat {e['flat_us']:.0f}us")
    return bad


def check_payload_accounting(res: dict) -> list:
    """Presets whose HLO payload ≠ the codec registry's wire_bits (must be
    empty), plus the §7.2 seed-only-overhead equalities."""
    bad = []
    presets = res["presets"]

    def wire_pl(name):
        # wire payload net of the scatter-decode gathers: the equalities
        # below are statements about the ENCODED message format, which
        # presets shipping scatter_decode (extra decoded-shard/counts
        # gathers, billed separately by scatter_bits) share unchanged.
        e = presets[name]
        return e["payload_bytes"] - e.get("scatter_payload_bytes", 0.0)

    for name, e in presets.items():
        if "accounted_payload_bytes" in e and \
                e["payload_bytes"] != e["accounted_payload_bytes"]:
            bad.append(f"{name}: payload={e['payload_bytes']:.0f}B "
                       f"!= accounting={e['accounted_payload_bytes']:.0f}B")
    for rot, plain in (("rotated_binary", "binary_packed"),
                       ("rotated_fixed_k", "fixed_k_gather")):
        # d is a power of two in this bench → wire payloads must be equal.
        if wire_pl(rot) != wire_pl(plain):
            bad.append(f"{rot}: wire payload != {plain} "
                       f"({wire_pl(rot):.0f} vs {wire_pl(plain):.0f})")
    for efp, plain in (("ef_fixed_k", "fixed_k_gather"),
                       ("ef_bernoulli", "bernoulli_seed_1bit"),
                       ("ef_binary", "binary_packed"),
                       ("ef_ternary", "ternary_packed"),
                       ("ef_rotated_binary", "rotated_binary"),
                       ("ternary_opt", "ternary_packed")):
        # EF residuals are local and the §6 ternary split rides the plane:
        # wire payload must equal the plain codec byte-for-byte.
        if wire_pl(efp) != wire_pl(plain):
            bad.append(f"{efp}: wire payload != {plain} "
                       f"({wire_pl(efp):.0f} vs {wire_pl(plain):.0f})")
    return bad


def rows():
    t0 = time.perf_counter()
    try:
        res = collect()
    except RuntimeError as e:
        dt = (time.perf_counter() - t0) * 1e6
        return [{"name": "collectives.wire_bytes", "us_per_call": dt,
                 "derived": f"FAILED: {str(e)[-300:]}", "check": False}]
    dt = (time.perf_counter() - t0) * 1e6
    p = res["presets"]
    exact = p["none"]["wire_bytes"]
    shared = p["fixed_k_1bit"]["wire_bytes"]
    gather = p["fixed_k_gather"]["wire_bytes"]
    # wire payloads net of the scatter-decode gathers (recorded separately
    # in scatter_payload_bytes): the ratios below compare message formats.
    def _wire_pl(name):
        return p[name]["payload_bytes"] - p[name].get(
            "scatter_payload_bytes", 0.0)
    dense_pl = _wire_pl("binary_dense")
    bin_pl = _wire_pl("binary_packed")
    tern_pl = _wire_pl("ternary_packed")
    rot_pl = _wire_pl("rotated_binary")
    bad = check_payload_accounting(res)
    t1 = time.perf_counter()
    try:
        sweep = collect_node_sweep()
    except RuntimeError as e:
        node_row = {"name": "collectives.node_sweep",
                    "us_per_call": (time.perf_counter() - t1) * 1e6,
                    "derived": f"FAILED: {str(e)[-300:]}", "check": False}
    else:
        nbad = check_node_scaling(sweep)
        top = max(sweep, key=int)
        e = sweep[top]["codecs"]["bernoulli"]
        node_row = {
            "name": "collectives.node_sweep",
            "us_per_call": (time.perf_counter() - t1) * 1e6,
            "derived": (f"n={top} bernoulli flat={e['flat_us']:.0f}us "
                        f"hier={e['hier_us']:.0f}us "
                        f"(x{e['flat_us'] / max(e['hier_us'], 1):.1f}); "
                        f"cross B flat={e['flat_payload_bytes']:.2e} "
                        f"hier={e['hier_cross_bytes']:.2e}"
                        + ("; " + "; ".join(nbad) if nbad else "")),
            # cross-host bytes == effective-n accounting at every n AND the
            # reduce-scatter decode beats flat gather wall-clock at the
            # largest n.
            "check": not nbad,
        }
    rb = res.get("robust", {})
    if rb:
        ovh = sorted(e["trim_overhead_x"] for e in rb.values())
        med = ovh[len(ovh) // 2]
        worst = max(rb, key=lambda k: rb[k]["trim_overhead_x"])
        robust_row = {
            "name": "collectives.robust_decode",
            "us_per_call": dt,
            "derived": (f"{len(rb)} gather presets; trim(1)/mean decode "
                        f"overhead min=x{ovh[0]:.2f} med=x{med:.2f} "
                        f"max=x{ovh[-1]:.2f} ({worst})"),
            # presence + sanity only: every gather preset reports positive
            # trimmed-decode timings (wall-clock ratios on fake devices
            # are too noisy for a tight gate).
            "check": all(e["trim1_us"] > 0 and e["trim2_us"] > 0
                         for e in rb.values()),
        }
    else:
        robust_row = {"name": "collectives.robust_decode",
                      "us_per_call": dt,
                      "derived": "FAILED: no robust section in sweep",
                      "check": False}
    return [
        {
            "name": "collectives.wire_bytes",
            "us_per_call": dt,
            "derived": (f"exact={exact:.3e}B shared={shared:.3e}B "
                        f"(x{exact / max(shared, 1):.1f} less) "
                        f"gather={gather:.3e}B (x{exact / max(gather, 1):.1f})"),
            # shared-support at k/d = 1/16 must cut ≥8x vs exact all-reduce
            "check": shared * 8 < exact,
        },
        {
            "name": "collectives.packed_planes",
            "us_per_call": dt,
            "derived": (f"dense_sim={dense_pl:.3e}B binary={bin_pl:.3e}B "
                        f"(x{dense_pl / max(bin_pl, 1):.1f} less) "
                        f"ternary={tern_pl:.3e}B "
                        f"(x{dense_pl / max(tern_pl, 1):.1f})"),
            # ≥8x payload reduction for the packed 1-bit plane vs the dense
            # f32 simulation.
            "check": bin_pl * 8 <= dense_pl,
        },
        {
            "name": "collectives.registry_accounting",
            "us_per_call": dt,
            "derived": (f"{len(p)} presets; rotated_binary={rot_pl:.3e}B "
                        f"(== binary_packed: {rot_pl == bin_pl}); "
                        + ("; ".join(bad) if bad else "payload==wire_bits "
                           "for every codec-backed preset")),
            # every preset's HLO payload equals the codec registry's
            # accounting; rotated presets cost exactly their inner codec.
            "check": not bad,
        },
        robust_row,
        node_row,
    ]
