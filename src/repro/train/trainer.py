"""Trainer: step loop with checkpoint/restart, async saves, straggler hooks.

Fault-tolerance contract (DESIGN.md §5):
  * every `ckpt_every` steps the full (params, opt, step, rng-cursor) state
    is saved with atomic commit (AsyncCheckpointer overlaps with compute);
  * on (re)start the trainer auto-resumes from the newest valid checkpoint
    — data is a pure function of step, so the stream realigns exactly;
  * elastic restart on a different mesh works via restore-time resharding;
  * straggler mitigation is structural: the production encoder is fixed-k
    (deterministic per-step bytes, §4.4) and `partial_mean` allows dropping
    a dead pod's contribution for a step without bias (core/collectives).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpointing as ckpt
from repro.configs.base import ArchConfig, RunConfig, ShapeSpec
from repro.data.pipeline import SyntheticLM
from repro.optim.optimizers import AdamWConfig
from repro.train import train_step as ts

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep_last: int = 3
    log_every: int = 10
    seed: int = 0


class Trainer:
    def __init__(self, mesh, cfg: ArchConfig, run: RunConfig,
                 shape: ShapeSpec, tcfg: TrainerConfig,
                 opt_cfg: Optional[AdamWConfig] = None):
        self.mesh = mesh
        self.cfg = cfg
        self.run = run
        self.shape = shape
        self.tcfg = tcfg
        # sync_plan is THE grad-sync plan the step executes (None =
        # per-leaf path): examples/diagnostics read bucket ids + the
        # readiness schedule from here.
        (self.step_fn, self.init_fn, self.specs, self.bspecs,
         self.sync_plan) = ts.build_train_step(mesh, cfg, run, shape,
                                               opt_cfg, base_seed=tcfg.seed)
        # (the schedule itself is logged by build_train_step)
        self.overlap = ts.overlap_enabled(self.sync_plan, run)
        self.data = SyntheticLM(cfg, shape, seed=tcfg.seed)
        self.ckpt = ckpt.AsyncCheckpointer()
        self.metrics_history = []
        # final error-feedback state (per-bucket residuals) after fit();
        # examples/diagnostics read the residual norms from here.
        self.ef_state = None

    def init_or_restore(self):
        params, opt_state, ef = self.init_fn(jax.random.PRNGKey(self.tcfg.seed))
        start = 0
        if self.tcfg.ckpt_dir and ckpt.latest_step(self.tcfg.ckpt_dir) is not None:
            start, params, opt_state, extra = ckpt.restore(
                self.tcfg.ckpt_dir, self.mesh, self.specs, opt_state)
            log.info("restored checkpoint at step %d", start)
        return start, params, opt_state, ef

    def fit(self):
        """Train from the newest checkpoint (or step 0) to ``tcfg.steps``.

        Each iteration is a profiler step (``train``) holding the host
        spans ``feed``, ``dispatch``, ``metrics_sync`` (the host reads the
        log step's metrics) and ``checkpoint``; they are recorded only
        while the profiler is on (``jax.profiler.trace``), on the
        clock of its device planes.
        """
        start, params, opt_state, ef = self.init_or_restore()
        t0 = time.time()
        for step in range(start, self.tcfg.steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with jax.profiler.TraceAnnotation("feed"):
                    batch = self.data.device_batch(step, self.mesh,
                                                   self.bspecs)
                with jax.profiler.TraceAnnotation("dispatch"):
                    params, opt_state, ef, metrics = self.step_fn(
                        params, opt_state, ef, batch, jnp.int32(step))
                if (step + 1) % self.tcfg.log_every == 0 or step == start:
                    with jax.profiler.TraceAnnotation("metrics_sync"):
                        m = {k: float(v) for k, v in metrics.items()}
                    m["step"] = step
                    m["sec"] = time.time() - t0
                    self.metrics_history.append(m)
                    log.info("step %d loss %.4f gnorm %.3f", step, m["loss"],
                             m["grad_norm"])
                if (self.tcfg.ckpt_dir
                        and (step + 1) % self.tcfg.ckpt_every == 0):
                    with jax.profiler.TraceAnnotation("checkpoint"):
                        self.ckpt.save(self.tcfg.ckpt_dir, step + 1, params,
                                       opt_state, self.specs,
                                       extra={"arch": self.cfg.name},
                                       keep_last=self.tcfg.keep_last)
        with jax.profiler.TraceAnnotation("checkpoint"):
            self.ckpt.wait()
            if self.tcfg.ckpt_dir:
                ckpt.save(self.tcfg.ckpt_dir, self.tcfg.steps, params,
                          opt_state, self.specs,
                          extra={"arch": self.cfg.name},
                          keep_last=self.tcfg.keep_last)
        self.ef_state = ef
        return params, opt_state, self.metrics_history
