"""Fault-tolerant training loop (deliverable b driver).

Production behaviors, exercised end-to-end by examples/quickstart.py and
tests/test_integration.py:

* auto-resume from the latest checkpoint (params/opt/step),
* periodic async checkpoints + graceful SIGTERM/SIGINT checkpoint
  (preemption handling),
* per-step deadline straggler mitigation: a step exceeding
  ``straggler_factor`` x the rolling median is logged and counted (on a
  real fleet this triggers the slow-host replacement hook),
* deterministic data (pure function of step) so recovery is exact,
* loss-spike skip: steps with non-finite loss are skipped (grad dropped),
  a standard large-run guard.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data import DataPipeline
from repro.models import lm
from repro.optim import init_opt_state
from .step import make_train_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    log_every: int = 10
    straggler_factor: float = 3.0
    seed: int = 0
    lr_peak: float = 3e-4
    lr_warmup: int = 200


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainerConfig,
                 pipeline: DataPipeline, mesh=None, shardings=None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.pipe = pipeline
        self.mesh = mesh
        self.ckpt = CheckpointManager(tcfg.ckpt_dir)
        self.step_fn = jax.jit(
            make_train_step(cfg, remat=True, lr_peak=tcfg.lr_peak,
                            lr_warmup=tcfg.lr_warmup,
                            lr_total=max(tcfg.steps, 10 * tcfg.lr_warmup)),
            donate_argnums=(0, 1))
        self._stop = False
        self.history: List[Dict] = []
        self.straggler_steps = 0

    def _install_signals(self):
        def handler(signum, frame):
            self._stop = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass  # not main thread (tests)

    def init_or_resume(self):
        key = jax.random.PRNGKey(self.tcfg.seed)
        params = lm.init_params(key, self.cfg)
        opt = init_opt_state(params)
        start = 0
        latest = self.ckpt.latest_step()
        if latest is not None:
            state = self.ckpt.restore({"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            start = latest
            print(f"[trainer] resumed from step {start}")
        return params, opt, start

    def run(self) -> Dict:
        self._install_signals()
        params, opt, start = self.init_or_resume()
        durations: List[float] = []
        final_loss = float("nan")
        step = start
        for step in range(start, self.tcfg.steps):
            if self._stop:
                print(f"[trainer] preemption signal: checkpointing @ {step}")
                break
            batch = self.pipe.batch(step)
            t0 = time.time()
            params, opt, metrics = self.step_fn(params, opt, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            durations.append(dt)
            med = float(np.median(durations[-20:]))
            if len(durations) > 5 and dt > self.tcfg.straggler_factor * med:
                self.straggler_steps += 1
                print(f"[trainer] straggler step {step}: {dt:.2f}s "
                      f"(median {med:.2f}s)")
            if not np.isfinite(loss):
                print(f"[trainer] non-finite loss at {step}; skipping")
                continue
            final_loss = loss
            if step % self.tcfg.log_every == 0:
                print(f"[trainer] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} {dt:.2f}s")
            self.history.append({"step": step, "loss": loss, "time": dt})
            if (step + 1) % self.tcfg.ckpt_every == 0:
                self.ckpt.save(step + 1, {"params": params, "opt": opt},
                               background=True)
        self.ckpt.save(step + 1, {"params": params, "opt": opt})
        self.ckpt.wait()
        return {"final_loss": final_loss, "steps_run": step + 1 - start,
                "stragglers": self.straggler_steps,
                "history": self.history}
