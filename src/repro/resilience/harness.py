"""Fault-injecting training harness for real sharded JAX training.

Closes the loop between the simulated recovery policies (PRs 1–5 score
``CheckpointRestore`` vs ``PeerTakeover`` inside the discrete-event
runtime) and what real sharded training actually survives: a
transformer config trains on an FSDP-style host-device mesh under a
deterministic :class:`~repro.resilience.schedule.FaultSchedule`; at a
scheduled step a data-parallel worker is lost mid-step, and the run
recovers through the *same policy objects* the event runtime scores,
via their ``real_apply`` hooks (``repro.serverless.recovery``):

  CheckpointRestore  the λML / MLLess model: the supervisor re-invokes
      the lost worker (the rebuilt full-width mesh), rolls the fleet
      back to the last mid-epoch ``repro.checkpoint`` snapshot and
      *replays* the lost steps.  With deterministic data the replayed
      trace is bit-identical to the uninterrupted same-seed run —
      the harness records the overlap for the regression tests.  With
      ``restore_reinvoke=False`` the snapshot restores onto the
      *shrunk survivor mesh* instead (sharded restore onto a different
      mesh; survivors then replay and absorb the dead partition).

  PeerTakeover  SPIRT (arXiv 2309.14148): per-worker state partitions
      live in the in-memory "in-DB" store
      (:class:`~repro.resilience.store.InMemoryStore`), pushed every
      ``push_every`` steps.  Survivors reassemble the current state
      from the store's bytes — the dead peer's partition is the one
      transfer recovery buys — re-shard it onto the survivor mesh
      (``sharding.survivor_mesh``) and continue *without replay*,
      absorbing the dead worker's minibatches.

Wall-clock accounting: both survivor-width and full-width step
functions are compiled during setup (``_warm``), so recovery wall times
measure state movement + replay — not XLA compilation, which is an
artifact of the single-process stand-in (a real SPIRT fleet's survivors
are warm processes, and a re-invoked Lambda's cold start is priced
separately by the event runtime's cold-start terms).
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.resilience.schedule import FaultSchedule
from repro.resilience.store import InMemoryStore


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """One resilient-training scenario (pure data, eagerly validated).

    ``arch`` names a ``repro.configs`` model (transformer family);
    ``sim_arch`` names the serverless :class:`~repro.serverless.archs.
    ArchSpec` twin — the harness trains with that spec's real-JAX
    strategy (``spec.make_strategy()``), so the simulated scenario and
    the real run share one architecture definition."""
    arch: str = "smollm-135m"
    sim_arch: str = "spirt"
    n_workers: int = 4
    steps: int = 12
    global_batch: int = 12
    seq: int = 16
    lr: float = 1e-2
    checkpoint_every: int = 4
    push_every: int = 1
    fsdp: bool = True
    reduced: bool = True
    restore_reinvoke: bool = True
    seed: int = 0

    def __post_init__(self):
        if self.n_workers < 2:
            raise ValueError(
                f"n_workers must be >= 2 (a one-worker fleet has no "
                f"survivors), got {self.n_workers}")
        if self.steps < 1:
            raise ValueError(f"steps must be >= 1, got {self.steps}")
        if self.checkpoint_every < 1 or self.push_every < 1:
            raise ValueError(
                f"checkpoint_every/push_every must be >= 1, got "
                f"{self.checkpoint_every}/{self.push_every}")
        if self.global_batch % self.n_workers:
            raise ValueError(
                f"global_batch {self.global_batch} must divide over "
                f"{self.n_workers} workers")
        if self.global_batch % (self.n_workers - 1):
            raise ValueError(
                f"global_batch {self.global_batch} must also divide "
                f"over {self.n_workers - 1} survivors (takeover "
                f"re-shards the same batch onto the shrunk fleet)")
        if self.seq < 2:
            raise ValueError(f"seq must be >= 2, got {self.seq}")


@dataclasses.dataclass
class RecoveryOutcome:
    """What one real recovery cost (one row of BENCH_recovery.json)."""
    step: int                       # kill step (in-flight work lost)
    worker: int
    mode: str                       # "restore" | "takeover"
    replayed_steps: int             # steps re-run from the snapshot
    wall_s: float                   # state movement + replay
    bytes_moved: int                # ckpt read | dead partition fetched
    n_workers_after: int
    ckpt_step: Optional[int] = None  # restore: snapshot rolled back to


@dataclasses.dataclass
class RunResult:
    """One training run (faulted or not) of the harness."""
    arch: str
    sim_arch: str
    losses: Tuple[float, ...]
    recoveries: List[RecoveryOutcome]
    n_params: int
    state_bytes: int                # serialized full-state blob size
    step_s: float                   # median fault-free step wall time
    n_workers_end: int
    replay_checks: Tuple[Tuple[int, float, float], ...] = ()
    # ^ (step, loss before kill, loss re-computed during replay)

    @property
    def final_loss(self) -> float:
        return self.losses[-1]

    @property
    def replay_exact(self) -> bool:
        """Every replayed step reproduced its pre-kill loss bit-exactly
        (vacuously true when nothing was replayed)."""
        return all(a == b for _, a, b in self.replay_checks)


class ResilientTrainer:
    """Drives one config through faulted/unfaulted runs.

    Construction compiles nothing; :meth:`run` owns the whole lifecycle
    (fresh state, fresh store, fresh checkpoint directory) so repeated
    calls with equal seeds replay bit-identically.
    """

    def __init__(self, config: ResilienceConfig,
                 ckpt_dir: Optional[str] = None):
        import jax

        from repro import optim
        from repro.configs.base import get_config
        from repro.data import lm_batches, token_stream
        from repro.models import build_model
        from repro.serverless.archs import get_arch

        self.config = config
        mcfg = get_config(config.arch)
        if config.reduced:
            mcfg = mcfg.reduced()
        if mcfg.family == "cnn":
            raise ValueError(
                f"{config.arch!r} is a CNN; the resilience harness "
                "targets the sharded transformer configs")
        self.model_config = mcfg
        self.model = build_model(mcfg, remat=False)
        self.optimizer = optim.adamw(config.lr)
        self.strategy = get_arch(config.sim_arch).make_strategy()
        devices = jax.devices()
        if len(devices) < config.n_workers:
            raise RuntimeError(
                f"need {config.n_workers} devices, have {len(devices)} "
                "(run under --xla_force_host_platform_device_count)")
        self._all_devices = tuple(devices[:config.n_workers])
        self._ckpt_dir = ckpt_dir or tempfile.mkdtemp(prefix="resil_")

        # deterministic per-step batches: a pure function of
        # (config.seed, step) — replay after restore re-reads the same
        # minibatches the lost steps consumed
        stream = token_stream(
            max(config.global_batch, 64) * (config.seq + 1) * 8,
            mcfg.vocab_size, seed=config.seed)
        it = lm_batches(stream, config.global_batch, config.seq,
                        seed=config.seed)
        self._batches = [next(it) for _ in range(config.steps)]

        # run-scoped state (set up by run())
        self.store = InMemoryStore()
        self._ts_cache: Dict[int, Any] = {}
        self._mesh = self._ts = self._state = None
        self._devices: Tuple = ()
        self._completed = 0
        self._losses: List[float] = []
        self._ckpt_steps: Dict[int, str] = {}
        self._replay_checks: List[Tuple[int, float, float]] = []

    # ------------------------------------------------------------------
    # mesh / step plumbing
    # ------------------------------------------------------------------
    def _build(self, devices):
        """(mesh, TrainStep) for a device tuple — FSDP-style: a pure
        data-parallel axis plus a width-1 'model' axis; param/optimizer
        leaves shard over 'data' where divisible (picodo idiom)."""
        from repro.core import build_train_step
        from repro.core.sharding import make_mesh
        mesh = make_mesh((len(devices), 1), ("data", "model"),
                         devices=devices)
        ts = build_train_step(self.model, self.optimizer, self.strategy,
                              mesh, fsdp=self.config.fsdp)
        return mesh, ts

    def _get_ts(self, devices):
        key = len(devices)
        if key not in self._ts_cache:
            self._ts_cache[key] = self._build(devices)
        return self._ts_cache[key]

    def _warm(self, devices):
        """Compile the step for this fleet width on throwaway state so
        recovery wall times exclude XLA compilation (see module doc)."""
        import jax
        _, ts = self._get_ts(devices)
        state = ts.init_state(jax.random.PRNGKey(0))
        ts.step_fn(state, self._put_batch(0, ts))

    def _put_batch(self, step, ts):
        import jax
        import jax.numpy as jnp
        return {k: jax.device_put(jnp.asarray(v), ts.batch_shardings[k])
                for k, v in self._batches[step].items()}

    def _do_step(self, step) -> float:
        self._state, m = self._ts.step_fn(
            self._state, self._put_batch(step, self._ts))
        return float(m["loss"])

    # ------------------------------------------------------------------
    # snapshots (checkpoint cadence + in-DB partitions)
    # ------------------------------------------------------------------
    def _snapshot(self):
        """Persist the current state: a mid-epoch checkpoint file every
        ``checkpoint_every`` completed steps (restore path) and the
        partitioned in-DB blob every ``push_every`` (takeover path)."""
        from repro import checkpoint
        c = self._completed
        if c % self.config.push_every == 0 or c == 0:
            self.store.push_partitions(checkpoint.dumps(self._state),
                                       len(self._devices))
        if c % self.config.checkpoint_every == 0:
            path = os.path.join(self._ckpt_dir, f"step_{c:06d}.msgpack")
            checkpoint.save(path, self._state)
            self._ckpt_steps[c] = path

    def _state_host(self) -> Any:
        """Current state as host numpy arrays (global view)."""
        import jax
        return jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                            self._state)

    def _adopt(self, host_state, mesh, ts, dead: Optional[int]):
        """Re-shard a host-side global state onto ``mesh`` via ``ts``'s
        shardings.  ``dead`` (takeover / shrunk restore) drops that
        worker's row from the per-worker strategy state — the survivors
        keep theirs, the dead peer's transient sync state is lost with
        it (SPIRT keeps durable state in the DB, which we restored)."""
        import jax
        import jax.numpy as jnp

        strat = host_state["strat"]
        if dead is not None:
            strat = jax.tree.map(lambda x: np.delete(x, dead, axis=0),
                                 strat)
        host_state = dict(host_state, strat=strat)
        self._mesh, self._ts = mesh, ts
        sds = ts.state_sds()
        self._state = jax.tree.map(
            lambda x, ref: jax.device_put(
                np.asarray(x), ref.sharding) if ref.sharding is not None
            else jnp.asarray(x),
            host_state, sds)

    # ------------------------------------------------------------------
    # recovery paths (driven by RecoveryPolicy.real_apply)
    # ------------------------------------------------------------------
    def recover_restore(self, worker: int) -> RecoveryOutcome:
        """Roll back to the last checkpoint and replay the lost steps.

        ``restore_reinvoke=True`` (default, the simulator's
        CheckpointRestore semantics): the dead worker is re-invoked, the
        full-width mesh is rebuilt, and the snapshot restores onto it —
        the replayed + continued trace is bit-identical to the
        uninterrupted same-seed run.  ``False``: the snapshot restores
        onto the *shrunk survivor mesh* (a genuinely different mesh than
        it was written from) and survivors replay, absorbing the dead
        partition — convergent, but not bit-comparable across widths.
        """
        from repro import checkpoint
        t0 = time.perf_counter()  # repro: allow[no-wallclock] -- measured recovery wall time is this harness's deliverable
        completed = self._completed
        ckpt_step = max(s for s in self._ckpt_steps if s <= completed)
        path = self._ckpt_steps[ckpt_step]
        replay = completed - ckpt_step

        if self.config.restore_reinvoke:
            devices = self._devices          # replacement fills the slot
            mesh, ts = self._get_ts(devices)
            # sharded restore straight onto the step's shardings: the
            # SDS template allocates nothing
            state = checkpoint.restore(path, like=ts.state_sds())
            self._mesh, self._ts, self._state = mesh, ts, state
        else:
            devices = (self._devices[:worker]
                       + self._devices[worker + 1:])
            mesh, ts = self._get_ts(devices)
            # restore to writable host arrays, then re-shard onto the
            # survivor mesh (strategy state loses the dead row)
            host = checkpoint.restore(path, like=self._host_template())
            self._devices = devices
            self._adopt(host, mesh, ts, dead=worker)

        self._completed = ckpt_step
        for t in range(ckpt_step, completed):
            loss = self._do_step(t)
            if t < len(self._losses):
                self._replay_checks.append((t, self._losses[t], loss))
                self._losses[t] = loss
            self._completed = t + 1
        wall = time.perf_counter() - t0  # repro: allow[no-wallclock] -- measured recovery wall time is this harness's deliverable
        return RecoveryOutcome(
            step=completed, worker=worker, mode="restore",
            replayed_steps=replay, wall_s=wall,
            bytes_moved=os.path.getsize(path),
            n_workers_after=len(self._devices), ckpt_step=ckpt_step)

    def recover_takeover(self, worker: int) -> RecoveryOutcome:
        """Survivors adopt the dead peer's in-DB partition and continue
        without replay on the shrunk mesh."""
        from repro import checkpoint
        t0 = time.perf_counter()  # repro: allow[no-wallclock] -- measured recovery wall time is this harness's deliverable
        completed = self._completed
        blob, dead_bytes = self.store.fetch_state(
            len(self._devices), dead=worker)
        host = checkpoint.loads(blob, like=self._host_template())
        devices = self._devices[:worker] + self._devices[worker + 1:]
        mesh, ts = self._get_ts(devices)
        self._devices = devices
        self._adopt(host, mesh, ts, dead=worker)
        wall = time.perf_counter() - t0  # repro: allow[no-wallclock] -- measured recovery wall time is this harness's deliverable
        return RecoveryOutcome(
            step=completed, worker=worker, mode="takeover",
            replayed_steps=0, wall_s=wall, bytes_moved=dead_bytes,
            n_workers_after=len(devices))

    def _host_template(self):
        """Writable numpy zero template matching the *current* global
        state (host-side restore target before re-sharding)."""
        import jax
        return jax.tree.map(
            lambda x: np.zeros(x.shape, dtype=x.dtype), self._state)

    # ------------------------------------------------------------------
    # the training loop
    # ------------------------------------------------------------------
    def run(self, schedule: Optional[FaultSchedule] = None,
            policy=None) -> RunResult:
        """One training run under ``schedule``; ``policy`` (a
        :class:`~repro.serverless.recovery.RecoveryPolicy`) defaults to
        the ``sim_arch``'s registry default (``recovery="auto"``)."""
        import jax

        cfg = self.config
        schedule = schedule or FaultSchedule()
        if policy is None and schedule.n_kills:
            from repro.serverless.runtime import default_recovery
            policy = default_recovery(
                cfg.sim_arch, checkpoint_every=cfg.checkpoint_every)
        for step, _ in schedule.kills:
            if step >= cfg.steps:
                raise ValueError(
                    f"kill at step {step} beyond the run's "
                    f"{cfg.steps} steps")

        # fresh lifecycle
        self.store.reset()
        self._ckpt_steps = {}
        self._replay_checks = []
        self._losses = []
        self._devices = self._all_devices
        self._warm(self._all_devices)
        if schedule.n_kills:
            self._warm(self._all_devices[:-1])
        self._mesh, self._ts = self._get_ts(self._devices)
        self._state = self._ts.init_state(jax.random.PRNGKey(cfg.seed))
        self._completed = 0
        self._snapshot()                       # step-0 rollback target

        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree.leaves(self._state["params"]))
        from repro import checkpoint
        state_bytes = len(checkpoint.dumps(self._state))

        recoveries: List[RecoveryOutcome] = []
        step_walls: List[float] = []
        step = 0
        while step < cfg.steps:
            w = schedule.kill_at(step)
            if w is not None and not any(r.step == step
                                         for r in recoveries):
                # mid-step loss: step's in-flight gradient work is
                # gone; the policy decides restore vs takeover
                recoveries.append(
                    policy.real_apply(self, w % len(self._devices)))
                step = self._completed   # restore may have rolled back
                continue
            t0 = time.perf_counter()  # repro: allow[no-wallclock] -- per-step wall cost feeds the chaos report
            loss = self._do_step(step)
            step_walls.append(time.perf_counter() - t0)  # repro: allow[no-wallclock] -- per-step wall cost feeds the chaos report
            if step < len(self._losses):
                self._losses[step] = loss
            else:
                self._losses.append(loss)
            self._completed = step + 1
            self._snapshot()
            step += 1

        return RunResult(
            arch=cfg.arch, sim_arch=cfg.sim_arch,
            losses=tuple(self._losses), recoveries=recoveries,
            n_params=n_params, state_bytes=state_bytes,
            step_s=float(np.median(step_walls)),
            n_workers_end=len(self._devices),
            replay_checks=tuple(self._replay_checks))
