"""Fault-tolerant training loop with first-class TALP monitoring.

Integration exactly mirrors the paper's GENE-X CI setup (§Integration),
expressed through the one instrumentation surface (``repro.session``): the
loop owns a ``PerfSession`` with an ``initialize`` region (compile +
restore) and a ``train_step`` region (the paper's ``timestep``) attached by
``session.wrap_step`` — which also derives the static StepProfile from the
compiled step and streams the per-step observables (tokens per shard,
expert loads, host heartbeat) into the collector. ``finalize_run(out_dir)``
writes the JSON artifact for TALP-Pages in one call.

Fault tolerance:
  * checkpoint every ``ckpt_every`` steps (async, atomic commit);
  * ``run()`` always restores the latest checkpoint when present — crash =
    restart the process, nothing else (the data pipeline is step-indexed);
  * straggler mitigation hook: when the measured host load balance drops
    below ``straggler_threshold`` the loop calls ``on_straggler`` (real
    deployment: re-shard away from the slow host / alert; tests assert the
    trigger);
  * ``fail_at_step`` injects a crash (used by the restart tests).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import ResourceConfig
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import devices_per_pod
from repro.session import PerfSession, SessionConfig
from repro.train.train import TrainConfig, compile_train_step, init_state


@dataclasses.dataclass
class LoopConfig:
    steps: int = 50
    ckpt_every: int = 0              # 0 = no checkpoints
    ckpt_dir: str = ""
    seed: int = 0
    straggler_threshold: float = 0.8
    monitor_app_name: str = "train"
    monitor_backend: str = "monitor"  # PerfSession backend (env can override)
    lb_sample_every: int = 1
    fail_at_step: int | None = None  # crash injection for restart tests
    host_times_fn: Callable[[int], Any] | None = None  # heartbeat source


class InjectedFailure(RuntimeError):
    pass


_UNSAMPLED = object()  # heartbeat not yet read for the current step


class TrainLoop:
    def __init__(
        self,
        cfg,
        mesh,
        tcfg: TrainConfig,
        data: SyntheticLM,
        loop_cfg: LoopConfig,
        on_straggler: Callable[[int, float], None] | None = None,
    ):
        self.cfg, self.mesh, self.tcfg = cfg, mesh, tcfg
        self.data = data
        self.loop = loop_cfg
        self.on_straggler = on_straggler
        self.straggler_events: list[tuple[int, float]] = []
        n = mesh.devices.size
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.resources = ResourceConfig(
            num_hosts=max(1, n // jax.local_device_count()),
            devices_per_host=min(n, jax.local_device_count()),
            mesh=sizes,
            num_pods=sizes.get("pod", 1),
        )
        self.session = PerfSession(
            SessionConfig(
                app_name=loop_cfg.monitor_app_name,
                backend=loop_cfg.monitor_backend,
                lb_sample_every=loop_cfg.lb_sample_every,
            ),
            self.resources,
        )
        self.ckpt = (
            CheckpointManager(loop_cfg.ckpt_dir) if loop_cfg.ckpt_dir else None
        )
        self.metrics_history: list[dict] = []
        self._cur_step = 0
        self._host_times: Any = _UNSAMPLED

    # ------------------------------------------------------------------

    def run(self) -> "TrainLoop":
        ses = self.session
        ses.start()
        with ses.region("initialize"):
            state, start_step, step_fn = self._initialize()

        try:
            for step in range(start_step, self.loop.steps):
                if self.loop.fail_at_step is not None and step == self.loop.fail_at_step:
                    raise InjectedFailure(f"injected failure at step {step}")
                batch = self.data.batch_at(step)
                self._cur_step = step
                self._host_times = _UNSAMPLED
                state, metrics = step_fn(state, batch)
                # the heartbeat is read post-step by _observe (inside the
                # train_step region); sample it here only when a null
                # backend skipped observation — straggler mitigation is a
                # loop feature, not an instrumentation feature
                if self._host_times is _UNSAMPLED:
                    self._host_times = self._sample_host_times()
                self._check_straggler(step, self._host_times)
                self.metrics_history.append(
                    {"step": step, "loss": float(metrics["loss"])}
                )
                if (
                    self.ckpt
                    and self.loop.ckpt_every
                    and (step + 1) % self.loop.ckpt_every == 0
                ):
                    self.ckpt.save(state, step + 1)
        finally:
            if self.ckpt:
                self.ckpt.wait()
            ses.stop()
        self.final_state = state
        return self

    # ------------------------------------------------------------------

    def _initialize(self):
        key = jax.random.PRNGKey(self.loop.seed)
        state = init_state(self.cfg, self.tcfg, key)
        state_tree = {
            "params": state.params, "opt_state": state.opt_state, "step": state.step
        }
        start = 0
        if self.ckpt and self.ckpt.latest() is not None:
            state_tree, start = self.ckpt.restore(state_tree)
        example = self.data.batch_at(0)
        compiled, call = compile_train_step(
            self.cfg, self.mesh, self.tcfg, state_tree, example
        )
        # lay the state out as the step takes it, here: init_state builds it
        # on one device, and the first step would otherwise reshard it inside
        # the train_step region (seconds on a multi-chip mesh)
        state_tree = jax.block_until_ready(
            jax.device_put(state_tree, compiled.input_shardings[0][0])
        )
        from repro.models.flops import train_step_model_flops

        step_fn = self.session.wrap_step(
            call,
            region="train_step",
            compiled=compiled,
            num_devices=self.mesh.devices.size,
            devices_per_pod=devices_per_pod(self.mesh),
            model_flops=train_step_model_flops(self.cfg, example["labels"].shape),
            observe=self._observe,
        )
        return state_tree, start, step_fn

    def _sample_host_times(self):
        """Read the per-host heartbeat for the step that just executed."""
        return (
            self.loop.host_times_fn(self._cur_step)
            if self.loop.host_times_fn
            else None
        )

    def _observe(self, out) -> dict:
        """Map one step result to the monitor observables (wrap_step hook;
        runs inside the train_step region, after the step executed)."""
        _state, metrics = out
        host_times = self._host_times = self._sample_host_times()
        return {
            "outputs": metrics,
            "tokens_per_shard": metrics.get("tokens_per_shard"),
            "expert_load": metrics.get("expert_load"),
            "host_times": host_times,
            "pod_size": (
                self.resources.num_hosts // self.resources.num_pods
                if host_times is not None and self.resources.num_pods > 1
                else None
            ),
        }

    def _check_straggler(self, step: int, host_times) -> None:
        if host_times is None:
            return
        arr = np.asarray(host_times, dtype=np.float64).reshape(-1)
        if arr.size < 2 or arr.max() <= 0:
            return
        lb = float(arr.mean() / arr.max())
        if lb < self.loop.straggler_threshold:
            self.straggler_events.append((step, lb))
            if self.on_straggler:
                self.on_straggler(step, lb)

    def finalize_run(self, out_dir: str | None = None):
        """One call for the whole artifact choreography: finalize the
        session's RunRecord and, when a destination resolves (``out_dir``,
        ``TALP_OUT``, or the session config), inject git metadata and save
        into the CI folder layout."""
        return self.session.finalize(out_dir)
