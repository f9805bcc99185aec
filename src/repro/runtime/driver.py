"""ElasticTrainer — the elastic self-adaptive training loop (paper fig. 11).

This is the reusable driver behind ``python -m repro.launch.train``: the
controller / sampler / hetero-step loop extracted from the CLI into an
object that also closes the paper's headline loop end-to-end:

* **Measurement-driven adaptation.** The controller consumes a
  :class:`~repro.runtime.monitor.TimingSource`.  By default that is
  :class:`MeasuredTimingSource` — real per-step wall clocks, attributed to
  ranks proportionally to the microbatches each computed (exact on one
  device; on a real fleet per-rank device fences replace the attribution).
  ``hetero_gpus`` swaps in :class:`SimulatedTimingSource` so a single CPU
  can exercise the heterogeneous trajectories.  A
  :class:`StragglerMonitor` rides along on the same measurements.

* **Membership changes.** A scripted event stream (``events="fail@8:3,
  add@16:v100,replace@24:0=v100"``, see
  :func:`~repro.runtime.elastic.parse_events`) and/or
  :class:`FailureDetector` heartbeats drive the full rescale path: barrier
  checkpoint -> :class:`RescalePlan` (survivor speeds carried, paper fig.
  11) -> rebuild mesh + step + batcher for the new worker count -> reshard
  params/optimizer state into the new layout -> continue at the same
  global step.  ``fail`` events go THROUGH the failure detector (the
  worker stops heartbeating and is declared dead after ``patience``
  intervals), so the production detection path is what gets exercised.

* **Fault injection.** ``faults="slow@8:2*3~6,netdeg@20:4~8,outage@30:1+2~5"``
  (see :func:`~repro.traces.faults.parse_faults`) layers degradation on
  top of the clean membership schedule: ``slow``/``netdeg`` windows
  perturb what the timing source REPORTS — the controller and the
  straggler monitor see injected slowness through the same measurement
  path as real slowness — and a correlated ``outage`` takes several
  workers through the failure detector in one rescale, rejoining them as
  adds (original GPU types) when the window heals.

* **Exact resume.** Checkpoints bundle model + optimizer state with the
  controller state (including its timing-log tail), the data position
  (epoch + aggregation index), and the current membership, so a restart
  resumes the run — same data order, same allocation, same fleet — instead
  of silently replaying epoch 0.  Resuming a run with scripted events
  requires passing the SAME event schedule; already-applied events are
  skipped via the persisted event cursor.

Epoch semantics: one "epoch" is one pass over the dataset —
``steps_per_epoch`` aggregations by default (``dataset_size`` overrides).
The controller reallocates at epoch boundaries only (paper Alg. 1); a
membership change mid-epoch ends the epoch early, because the surviving
fleet cannot finish a data partition laid out for the old membership.
"""

from __future__ import annotations

import dataclasses
import json
import time

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, smoke_config
from repro.core import (
    AdaptiveAllocationController,
    ClusterSpec,
    ControllerConfig,
    equal_allocation,
    static_allocation,
)
from repro.data import HeteroBatcher, SyntheticLM
from repro.dist import HeteroStepConfig, build_train_step, init_train_state, micro_passes
from repro.dist.collectives import ring_allreduce_bytes
from repro.dist.sharding import state_specs
from repro.obs import TrainObs
from repro.launch.mesh import make_test_mesh
from repro.optim import warmup_cosine
from repro.core.hetero import normalize_gpu
from repro.runtime.elastic import (
    ElasticCoordinator,
    FailureDetector,
    MembershipEvent,
    parse_events,
    validate_schedule,
)
from repro.runtime.monitor import (
    MeasuredTimingSource,
    SimulatedTimingSource,
    StragglerMonitor,
)
from repro.traces.faults import FaultEvent, FaultInjector, FaultyTimingSource, parse_faults

__all__ = ["DriverConfig", "ElasticTrainer"]

# Simulated collective seconds per aggregation (eq. 2's t_c; matches the
# benchmark harness).  Measured mode folds collective time into the wall
# clock and reports t_c=0.
_T_C_SIM = 0.1


@dataclasses.dataclass(frozen=True)
class DriverConfig:
    """Everything the CLI can say, as data (``launch/train.py`` is a thin
    argparse shim over this)."""

    arch: str
    smoke: bool = False
    steps: int = 40
    seq: int = 64
    n_workers: int = 4
    micro_bs: int = 4
    total_micro: int = 16  # C: microbatches per aggregation, constant (eq. 4)
    w_max: int = 0  # 0 -> auto (2C/n, grown on demand)
    policy: str = "adaptive"  # "adaptive" | "equal" | "static"
    static_ratio: str | None = None
    mode: str = "masked"  # "masked" | "while"
    fsdp: str = "none"  # "none" | "gather"
    hetero_gpus: str | None = None  # comma GPU names -> simulated timing
    steps_per_epoch: int = 4  # aggregations per dataset pass (epoch)
    dataset_size: int = 0  # 0 -> total_micro * micro_bs * steps_per_epoch
    lr: float = 3e-4
    ckpt_dir: str | None = None
    ckpt_every: int = 20
    resume: bool = False
    seed: int = 0
    events: str | None = None  # scripted membership schedule
    faults: str | None = None  # scripted fault schedule (slow/netdeg/outage + membership)
    heartbeat_patience: int = 3
    log_every: int = 10
    verbose: bool = True
    trace_out: str | None = None  # Perfetto trace-event JSON path
    metrics_out: str | None = None  # metrics snapshot JSON path


class ElasticTrainer:
    """One training job: fixed C, elastic membership.

    Construct, then :meth:`run`.  The constructor restores from the latest
    checkpoint when ``cfg.resume`` — including the checkpointed MEMBERSHIP,
    which wins over ``cfg.n_workers`` if events had already reshaped the
    fleet before the restart.
    """

    def __init__(self, cfg: DriverConfig) -> None:
        # config validation up front (the CLI has its own argparse guards,
        # but the driver is the advertised programmatic entry point)
        if cfg.policy not in ("adaptive", "equal", "static"):
            raise ValueError(f"policy must be adaptive/equal/static, got {cfg.policy!r}")
        if cfg.policy == "static" and not cfg.static_ratio:
            raise ValueError("policy='static' requires static_ratio (e.g. '6,4')")
        if cfg.fsdp == "gather" and cfg.mode != "while":
            raise ValueError("fsdp='gather' pairs with mode='while'")
        if cfg.heartbeat_patience < 1:
            raise ValueError(
                "heartbeat_patience must be >= 1 — with zero patience the failure "
                "detector never declares anyone dead and fail events become silent no-ops"
            )
        self.cfg = cfg
        self.model_cfg = smoke_config(cfg.arch, seq=cfg.seq) if cfg.smoke else get_config(cfg.arch)
        self.C = cfg.total_micro
        self.seq_len = cfg.seq if cfg.smoke else self.model_cfg.max_seq
        self.simulated = cfg.hetero_gpus is not None

        scripted: list = parse_events(cfg.events) if cfg.events else []
        if cfg.faults:
            scripted = scripted + parse_faults(cfg.faults)
        # one validated schedule: a --faults step colliding with an --events
        # step is exactly as order-dependent as two --events terms colliding
        self.events: list = validate_schedule(scripted)
        self._schedule_specs = [e.spec() for e in self.events]  # static schedule (fingerprint)
        self._event_idx = 0

        # -- initial membership ------------------------------------------------
        gpus = (cfg.hetero_gpus or ",".join(["rtx2080ti"] * cfg.n_workers)).split(",")
        self.gpus = [normalize_gpu(g) for g in gpus]  # typos fail HERE, not in _build
        self.gpus0 = list(self.gpus)  # the job's INITIAL fleet (resume fingerprint)
        if cfg.hetero_gpus is not None and len(self.gpus) != cfg.n_workers:
            raise ValueError(
                f"hetero_gpus lists {len(self.gpus)} workers but n_workers={cfg.n_workers}; "
                "make them agree — the GPU list defines the fleet, so a silent mismatch "
                "would train the wrong worker count"
            )
        self.ctl = AdaptiveAllocationController(ControllerConfig(total=self.C, n_workers=len(self.gpus), w_min=1))
        if cfg.policy == "static":
            ratios = [float(x) for x in (cfg.static_ratio or "").split(",")]
            self.alloc = static_allocation(ratios, self.C)
        else:
            self.alloc = self.ctl.allocation

        # -- data: one dataset object outlives every membership ---------------
        size = cfg.dataset_size or self.C * cfg.micro_bs * max(cfg.steps_per_epoch, 1)
        if size % cfg.micro_bs or size < self.C * cfg.micro_bs:
            raise ValueError(
                f"dataset_size={size} must be a multiple of micro_bs={cfg.micro_bs} "
                f"and hold at least one aggregation ({self.C * cfg.micro_bs} samples)"
            )
        self.dataset = SyntheticLM(
            vocab_size=self.model_cfg.vocab_size,
            seq_len=self.seq_len,
            n_sequences=size,
            seed=cfg.seed,
        )

        # -- position + bookkeeping -------------------------------------------
        self.step_i = 0
        self.epoch = 0
        self.agg_index = 0  # aggregations already consumed in the current epoch
        self.losses: list[float] = []
        self.grad_norms: list[float] = []  # global gradient norm of each step, before clipping
        self.step_s: list[float] = []  # wall seconds of each step (the first compiles)
        # microbatch passes the steps computed, and the allocation's share of
        # them (masked mode computes max(alloc) passes per rank, padding included)
        self.micro_passes_computed = 0
        self.micro_passes_trained = 0
        self.epoch_log: list[dict] = []  # completed epochs (BENCH reads this)
        self.membership_log: list[dict] = []
        self.straggler_flags = 0
        self.straggler_log: list[dict] = []  # survives monitor rebuilds
        self.fd = FailureDetector(len(self.gpus), patience=cfg.heartbeat_patience)
        self.injector = FaultInjector(len(self.gpus)) if cfg.faults else None
        self.fault_log: list[dict] = []

        # -- checkpointing / resume -------------------------------------------
        self.mgr = CheckpointManager(cfg.ckpt_dir, save_every=cfg.ckpt_every) if cfg.ckpt_dir else None
        # state tree shape is membership-independent, so a pre-event "like"
        # tree restores checkpoints written under any later membership
        like_scfg = HeteroStepConfig(w_max=1, micro_bs=cfg.micro_bs, seq_len=self.seq_len, optimizer="adamw")
        self.state = init_train_state(self.model_cfg, like_scfg, jax.random.PRNGKey(cfg.seed))
        # observability: virtual-clock spans/metrics, no-op unless requested
        self.obs = TrainObs(cfg.trace_out, cfg.metrics_out)
        self._param_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self.state["params"]))
        if self.mgr and cfg.resume and self.mgr.latest_step() is not None:
            self._restore()
        self._rebuild()

    # -- membership-dependent construction ------------------------------------

    def _rebuild(self) -> None:
        with TraceAnnotation("driver.rebuild"):
            self._build()
            self._reshard_state()

    def _build(self) -> None:
        """(Re)build everything that depends on the current membership:
        mesh, step config/function, batcher, timing source, monitor."""
        cfg = self.cfg
        n = len(self.gpus)
        auto = max(2 * self.C // n, self.C // n + 1)
        # grow past an explicit w_max rather than reject a legal allocation
        self.w_max = max(cfg.w_max or auto, int(np.max(self.alloc)))
        n_dev = len(jax.devices())
        shape = (n, 1) if 1 < n <= n_dev else (1, 1)
        if n > 1 and shape == (1, 1):
            # more ranks than devices: every rank's rows run on one device
            # (the step is allocation-invariant, so the math is unchanged)
            self._log(f"[mesh] {n} ranks folded onto a 1x1 mesh ({n_dev} device(s) visible)")
        self.mesh = make_test_mesh(shape, ("data", "model"))
        # rank-major batch rows live on their rank's device: while-mode's
        # shard_map takes them as they are, and masked mode's vmap over ranks
        # is partitioned by GSPMD instead of running every rank on one device
        self._batch_sharding = NamedSharding(self.mesh, P("data"))
        self.scfg = HeteroStepConfig(
            w_max=self.w_max,
            micro_bs=cfg.micro_bs,
            seq_len=self.seq_len,
            mode=cfg.mode,
            alloc_axis="data",
            fsdp="gather" if cfg.fsdp == "gather" else False,
            fsdp_axes=("data",),
            optimizer="adamw",
        )
        self.step_fn = build_train_step(
            self.model_cfg,
            self.scfg,
            self.mesh,
            lr_fn=warmup_cosine(cfg.lr, 10, cfg.steps),
            jit=True,
        )
        self.batcher = HeteroBatcher(self.dataset, n, cfg.micro_bs, self.w_max, seed=cfg.seed)
        self._rebuild_monitoring()

    def _rebuild_monitoring(self) -> None:
        """(Re)create the timing source + straggler monitor for the current
        fleet — the cheap half of a rebuild, sufficient on its own when the
        membership's SHAPE (worker count, buffer depth) did not change."""
        n = len(self.gpus)
        if self.simulated:
            self.timing = SimulatedTimingSource(ClusterSpec.from_gpus(self.gpus, seed=self.cfg.seed))
        else:
            self.timing = MeasuredTimingSource(n)
        # A fresh measured source only covers steps from the CURRENT data
        # position onward; _finish_epoch must not treat a from-mid-epoch
        # accumulation (post-resume) as a full epoch measurement.
        self._timing_from_agg = self.agg_index
        if self.injector is not None:
            # fault windows perturb what the controller MEASURES, whatever
            # the inner source is — injected stragglers ride the real path
            self.timing = FaultyTimingSource(self.timing, self.injector, lambda: self.step_i)
        self.straggler = StragglerMonitor(n)

    def _reshard_state(self) -> None:
        """Place the persistent state for the current mesh, where the step
        returns it: sharded per ``state_specs`` under ``fsdp='gather'``,
        replicated otherwise.  Placed as the step's output will be, the state
        never reaches the step in a second layout, so the step compiles once.
        After a membership change the old layout no longer matches, so the
        whole tree is re-placed (jax reshards across mesh shapes in one
        device_put per leaf)."""
        if self.scfg.fsdp == "gather":
            sspecs = state_specs(self.state, self.mesh, fsdp=True, fsdp_axes=self.scfg.fsdp_axes)
        else:
            sspecs = jax.tree.map(lambda _: P(), self.state)
        self.state = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)), self.state, sspecs)

    # -- checkpoint metadata ----------------------------------------------------

    def _metadata(self) -> dict:
        meta = {
            "controller": self.ctl.state_dict(),
            "epoch": self.epoch,
            "agg_index": self.agg_index,
            "gpus": list(self.gpus),
            "alloc": np.asarray(self.alloc).tolist(),
            "events_applied": self._event_idx,
            "policy": self.cfg.policy,
            "timing": "simulated" if self.simulated else "measured",
            "data": self._data_fingerprint(),
        }
        if self.injector is not None:
            # the LIVE schedule (static + dynamic recovery adds an outage
            # scheduled) and the open fault windows — the event cursor
            # indexes into this schedule, not the static one
            meta["faults"] = {
                "injector": self.injector.state_dict(),
                "schedule": [e.spec() for e in self.events],
            }
        return meta

    def _data_fingerprint(self) -> dict:
        """Everything that defines the run a checkpoint position points into:
        the data stream (a resume under different values would replay/skip
        samples while claiming the checkpointed epoch/aggregation position),
        the INITIAL fleet (the current fleet legitimately drifts via events,
        but the job's starting fleet must match or the user's changed
        --hetero-gpus would be silently discarded), and the event schedule
        (the persisted cursor indexes into it — a reordered/edited schedule
        would mis-apply events)."""
        return {
            "seed": self.cfg.seed,
            "dataset_size": len(self.dataset),
            "total_micro": self.C,
            "micro_bs": self.cfg.micro_bs,
            "seq_len": self.seq_len,
            "gpus0": list(self.gpus0),
            "events": list(self._schedule_specs),
        }

    def _restore(self) -> None:
        self.step_i, self.state, meta = self.mgr.restore(self.state)
        ctl_state = meta["controller"]
        if isinstance(ctl_state, str):  # pre-driver checkpoints json.dumps'd it
            ctl_state = json.loads(ctl_state)
        self.ctl = AdaptiveAllocationController.from_state_dict(ctl_state)
        ckpt_policy = meta.get("policy", self.cfg.policy)
        if ckpt_policy != self.cfg.policy:
            raise ValueError(
                f"checkpoint was written under policy={ckpt_policy!r} but this run asks "
                f"for policy={self.cfg.policy!r}; resuming would train on an allocation "
                "the flags never requested — restart without --resume to switch policy"
            )
        this_timing = "simulated" if self.simulated else "measured"
        ckpt_timing = meta.get("timing", this_timing)
        if ckpt_timing != this_timing:
            raise ValueError(
                f"checkpoint was written under {ckpt_timing} timing but this run uses "
                f"{this_timing} (--hetero-gpus changed?); the restored controller log "
                "carries the other mode's speed units — resume with the original flags"
            )
        this_data = self._data_fingerprint()
        ckpt_data = meta.get("data", this_data)
        if ckpt_data != this_data:
            diff = {k: (v, this_data[k]) for k, v in ckpt_data.items() if this_data.get(k) != v}
            raise ValueError(
                f"checkpoint's data stream does not match this run's flags: "
                f"{{field: (checkpoint, now)}} = {diff}; the restored epoch/aggregation "
                "position (and event cursor) would point into a different run — resume "
                "with the original seed/dataset/batch/fleet/--events flags"
            )
        # data position: without these two, every restart replayed the run's
        # data from epoch 0, aggregation 0
        self.epoch = int(meta.get("epoch", 0))
        self.agg_index = int(meta.get("agg_index", 0))
        self.gpus = list(meta.get("gpus", self.gpus))
        self.alloc = np.asarray(meta.get("alloc", self.ctl.allocation), dtype=np.int64)
        self._event_idx = int(meta.get("events_applied", 0))
        if self.injector is not None and "faults" in meta:
            # the checkpointed schedule may carry dynamic recovery adds the
            # static --faults string does not; the cursor indexes into IT
            self.injector = FaultInjector.from_state_dict(meta["faults"]["injector"])
            sched = ",".join(meta["faults"]["schedule"])
            self.events = parse_faults(sched) if sched else []
        if self._event_idx > len(self.events):
            raise ValueError(
                f"checkpoint had {self._event_idx} events applied but --events "
                f"lists only {len(self.events)}; resume with the original schedule"
            )
        self.fd = FailureDetector(len(self.gpus), patience=self.cfg.heartbeat_patience)
        self._log(
            f"[resume] step {self.step_i}, epoch {self.epoch} agg {self.agg_index}, "
            f"fleet {self.gpus}, allocation {np.asarray(self.alloc).tolist()}"
        )

    # -- membership events -------------------------------------------------------

    def _event_due(self) -> bool:
        return self._event_idx < len(self.events) and self.events[self._event_idx].step <= self.step_i

    def _apply_due_events(self) -> bool:
        applied = False
        while self._event_due():
            self._apply_event(self.events[self._event_idx])
            self._event_idx += 1
            applied = True
        return applied

    def _est_speed(self, gpu: str) -> float | None:
        """Joiner speed estimate in the units the controller's log carries:
        simulated speeds ARE model throughputs, so a one-card cluster from
        the same constructor gives an estimate in the fleet's own units;
        measured speeds have no table to consult, so the joiner warm-starts
        at the fleet mean (coordinator default)."""
        if self.simulated:
            return ClusterSpec.from_gpus([gpu]).workers[0].throughput
        return None

    def _apply_event(self, ev: MembershipEvent | FaultEvent) -> None:
        if ev.kind in ("slow", "netdeg"):
            # timing faults perturb measurements, not membership: no barrier
            # checkpoint, no early epoch boundary, no rebuild
            self.injector.apply(ev)
            self.fault_log.append({"step": self.step_i, "fault": ev.spec()})
            self.obs.on_fault(self.step_i, ev.spec(), getattr(ev, "duration", None))
            self._log(f"[fault] step {self.step_i}: {ev.spec()} active")
            return

        n = len(self.gpus)
        victims = sorted(getattr(ev, "workers", ()))
        if ev.kind in ("fail", "replace") and not (0 <= ev.index < n):
            raise ValueError(f"event {ev}: worker index out of range for membership size {n}")
        if ev.kind == "outage" and (not victims or victims[-1] >= n):
            raise ValueError(f"event {ev}: outage workers {victims} out of range for membership size {n}")
        if (ev.kind == "fail" and n == 1) or (ev.kind == "outage" and len(victims) >= n):
            raise ValueError(f"event {ev}: cannot fail the last remaining worker — the fleet would be empty")

        # Barrier checkpoint with PRE-event metadata: a crash during the
        # rebuild window resumes just before the event and re-applies it
        # (the event cursor saved here still points at this event).
        if self.mgr:
            self.mgr.save(self.step_i, self.state, metadata=self._metadata())
            self.obs.on_checkpoint(self.step_i)
        if ev.kind == "outage":
            # an outage is both a membership change and a fault window
            self.obs.on_fault(self.step_i, ev.spec(), getattr(ev, "duration", None))

        coord = ElasticCoordinator(self.ctl)
        if ev.kind in ("fail", "outage"):
            # through the detector: the silent workers stop heartbeating and
            # are declared dead after `patience` missed intervals — an outage
            # is the correlated case, one rescale for the whole group
            silent = set(victims or [ev.index])
            dead: list[int] = []
            for _ in range(self.fd.patience):
                for w in range(self.fd.n_workers):
                    if w not in silent and self.fd.alive[w]:
                        self.fd.heartbeat(w)
                dead = self.fd.tick() or dead
            plan = coord.remove(dead, restore_step=self.step_i)
            new_gpus = [self.gpus[i] for i in plan.survivors]
            if ev.kind == "outage" and ev.duration is not None:
                # the outage heals: victims rejoin as adds with their own
                # GPU types, `duration` steps out
                self._schedule_recovery([self.gpus[i] for i in sorted(silent)], self.step_i + ev.duration)
        elif ev.kind == "add":
            plan = coord.add(1, est_speed=self._est_speed(ev.gpu))
            new_gpus = self.gpus + [ev.gpu]
        else:  # replace
            plan = coord.replace(ev.index, est_speed=self._est_speed(ev.gpu))
            new_gpus = list(self.gpus)
            new_gpus[ev.index] = ev.gpu

        self.fd.rescale(plan.survivors, plan.n_new)
        if self.injector is not None:
            # slow windows are slot-indexed like the detector's miss counts
            self.injector.rescale(plan.survivors, plan.n_new)
        if ev.kind == "replace":
            self.fd.heartbeat(ev.index)  # fresh card in that slot: clean miss count
        self.gpus = new_gpus
        if self.cfg.policy == "equal":
            # the equal policy is a statement about the allocation, not the
            # fleet: re-apply it to the new membership
            self.alloc = equal_allocation(len(new_gpus), self.C)
        else:
            # adaptive takes the warm-started plan; static does too — a
            # --static-ratio no longer matches the fleet it was written for
            # once the fleet changes
            self.alloc = np.asarray(plan.allocation, dtype=np.int64)
        if self.agg_index:
            # mid-epoch: the remaining partition belongs to the old
            # membership — reallocate data at the (early) epoch boundary,
            # as the paper does
            self.epoch += 1
            self.agg_index = 0
        detail: dict = {"index": ev.index, "gpu": ev.gpu}
        if victims:
            detail["workers"] = victims
        self.membership_log.append(
            {
                "step": self.step_i,
                "event": f"{ev.kind}@{ev.step}",
                "detail": detail,
                "gpus": list(self.gpus),
                "allocation": self.alloc.tolist(),
            }
        )
        self.obs.on_membership(self.step_i, f"{ev.kind}@{ev.step}", self.gpus, self.alloc)
        self._log(f"[elastic] step {self.step_i}: {ev.kind} -> fleet {self.gpus}, allocation {self.alloc.tolist()}")
        if len(self.gpus) == n and int(np.max(self.alloc)) <= self.w_max:
            # same worker count and the new allocation fits the existing
            # buffers (the common replace case): the compiled step, mesh and
            # batcher are all still valid — skip the XLA recompile and only
            # re-point the speed model / monitor at the new fleet
            self._rebuild_monitoring()
        else:
            self._rebuild()

    def _schedule_recovery(self, gpus: list[str], at_step: int) -> None:
        """Insert dynamic ``add`` events for healed outage victims, each on
        its own free step (the validated schedule owns every step), keeping
        the applied prefix of ``self.events`` untouched."""
        used = {e.step for e in self.events}
        step = max(at_step, self.step_i + 1)
        for gpu in gpus:
            while step in used:
                step += 1
            used.add(step)
            ev = FaultEvent(step=step, kind="add", gpu=gpu)
            self.events.append(ev)
            self.fault_log.append({"step": self.step_i, "fault": f"recovery scheduled: {ev.spec()}"})
            self._log(f"[fault] step {self.step_i}: outage heals at step {step} ({gpu} rejoins)")
        # re-sort the pending tail; applied events all precede step_i < new
        # steps, so the cursor's prefix is stable and steps stay unique
        self.events = validate_schedule(self.events)

    # -- the loop -----------------------------------------------------------------

    def run(self) -> dict:
        cfg = self.cfg
        t_wall = time.time()
        while self.step_i < cfg.steps:
            if self._apply_due_events():
                continue
            self._run_epoch()
        if self.mgr:
            # terminal checkpoint so a follow-up --resume with more --steps
            # continues instead of recomputing from the last periodic save
            self.mgr.save(self.step_i, self.state, metadata=self._metadata())
            self.obs.on_checkpoint(self.step_i)
        self.obs.close()
        result = {
            "arch": self.model_cfg.name,
            "steps": self.step_i,
            "epoch": self.epoch,
            "agg_index": self.agg_index,
            "first_loss": self.losses[0] if self.losses else None,
            "last_loss": self.losses[-1] if self.losses else None,
            "loss_drop": (self.losses[0] - self.losses[-1]) if self.losses else None,
            "losses": list(self.losses),
            "grad_norms": list(self.grad_norms),
            "step_s": list(self.step_s),
            "mesh": self._mesh_summary(),
            "final_allocation": np.asarray(self.alloc).tolist(),
            "n_workers": len(self.gpus),
            "gpus": list(self.gpus),
            "controller_frozen": self.ctl.frozen,
            "timing": "simulated" if self.simulated else "measured",
            "epoch_log": self.epoch_log,
            "epoch_summary": self._epoch_summary(),
            "memberships": self.membership_log,
            "events_applied": self._event_idx,
            "events_pending": len(self.events) - self._event_idx,
            "straggler_flags": self.straggler_flags,
            "straggler_log": self.straggler_log,
            "fault_log": self.fault_log,
            "wall_s": round(time.time() - t_wall, 1),
        }
        return result

    def _run_epoch(self) -> None:
        """Train until the epoch completes, an event comes due, or the step
        budget runs out.  Controller updates happen only on COMPLETE epoch
        measurements.

        Host spans on the profiler's clock (recorded only while a JAX profile
        is taken): ``driver.batch`` around each pull from the batcher, the
        look-ahead pull that the budget or an event then drops included;
        ``driver.step`` (``step_num``) around each step, holding
        ``driver.put`` / ``driver.dispatch`` / ``driver.sync`` /
        ``driver.record``; ``driver.epoch_end`` around the epoch boundary."""
        cfg = self.cfg
        alloc = np.asarray(self.alloc)
        n_agg = self.batcher.aggregations_per_epoch(alloc)
        steps_run = 0
        batches = self.batcher.epoch(self.epoch, alloc, start=self.agg_index)
        while True:
            with TraceAnnotation("driver.batch"):
                batch_np = next(batches, None)
            if batch_np is None:
                break
            if self.step_i >= cfg.steps or self._event_due():
                return  # leave agg_index where it is; caller decides
            with StepTraceAnnotation("driver.step", step_num=self.step_i):
                with TraceAnnotation("driver.put"):
                    batch = jax.device_put(
                        {k: batch_np[k] for k in ("inputs", "targets", "alloc")}, self._batch_sharding
                    )
                t0 = time.perf_counter()
                with TraceAnnotation("driver.dispatch"):
                    self.state, metrics = self.step_fn(self.state, batch)
                with TraceAnnotation("driver.sync"):
                    loss = float(metrics["loss"])  # device sync: wall below is honest
                    grad_norm = float(metrics["grad_norm"])
                dt = time.perf_counter() - t0
                with TraceAnnotation("driver.record"):
                    self.timing.record_step(dt, batch_np["alloc"])
                    self.losses.append(loss)
                    self.grad_norms.append(grad_norm)
                    self.step_s.append(dt)
                    computed = micro_passes(self.scfg, batch_np["alloc"])
                    trained = int(batch_np["alloc"].sum())
                    self.micro_passes_computed += computed
                    self.micro_passes_trained += trained
                    self.obs.on_micro_passes(computed, trained)
                    self.step_i += 1
                    self.agg_index += 1
                    steps_run += 1
                    # the metadata (controller state_dict + log tail) is only worth
                    # serializing on steps that actually save
                    if self.mgr and self.mgr.is_due(self.step_i):
                        self.mgr.save(self.step_i, self.state, metadata=self._metadata())
                        self.obs.on_checkpoint(self.step_i)
                    if self.step_i % cfg.log_every == 0 or self.step_i == 1:
                        self._log(
                            f"step {self.step_i:5d} loss {loss:.4f} "
                            f"tokens {float(metrics['tokens']):.0f} alloc {alloc.tolist()}"
                        )
        if self.agg_index >= n_agg:
            with TraceAnnotation("driver.epoch_end"):
                self._finish_epoch(steps_run, n_agg)

    def _finish_epoch(self, steps_run: int, n_agg: int) -> None:
        """Epoch boundary: read the timing source, update the controller
        (Alg. 1 steps 1-3), advance the data position."""
        alloc = np.asarray(self.alloc)
        complete = self.simulated or self._timing_from_agg == 0
        if self.timing.ready and complete:
            t_s = self.timing.epoch_times(alloc, self.epoch)
            t_c = _T_C_SIM if self.simulated else 0.0
            # an active netdeg fault scales the collective model (measured
            # mode folds collectives into the wall clock; nothing to scale)
            t_c *= getattr(self.timing, "last_collective_scale", 1.0)
            flags = self.straggler.observe(t_s / np.maximum(alloc, 1), epoch=self.epoch, step=self.step_i)
            self.straggler_flags += len(flags)
            for f in flags:
                self.straggler_log.append(
                    {
                        "epoch": self.epoch,
                        "step_end": self.step_i,
                        "worker": f.worker,
                        "z": round(f.z_score, 2),
                        "persistent": f.persistent,
                        "observed": round(f.observed, 6),
                        "baseline": round(f.baseline, 6),
                    }
                )
                self._log(
                    f"[straggler] epoch {self.epoch}: worker {f.worker} "
                    f"z={f.z_score:.1f} persistent={f.persistent}"
                )
            # per-aggregation makespan: simulated t_s is per aggregation,
            # measured t_s is the epoch's accumulated wall per rank
            agg_s = float(np.max(t_s)) + t_c
            if not self.simulated and steps_run > 0:
                agg_s = float(np.max(t_s)) / steps_run
            if steps_run > 0:
                # a resume can land exactly at an epoch's last aggregation
                # (saved after the step, before _finish_epoch): the controller
                # update below is still due, but logging a 0-step epoch would
                # inflate epoch_summary / the BENCH curve with phantom time
                self.epoch_log.append(
                    {
                        "epoch": self.epoch,
                        "n_workers": len(self.gpus),
                        "gpus": list(self.gpus),
                        "alloc": alloc.tolist(),
                        "agg_s": agg_s,
                        "epoch_s": agg_s * n_agg,
                        "steps": steps_run,
                        "step_end": self.step_i,  # fault campaigns date epochs in steps
                    }
                )
            if self.obs.enabled and steps_run > 0:
                self.obs.on_epoch(
                    self.epoch,
                    self.step_i,
                    steps_run,
                    [float(t) for t in t_s],
                    t_c,
                    alloc,
                    self.gpus,
                    per_agg=self.simulated,
                    coll_bytes=ring_allreduce_bytes(self._param_bytes, len(self.gpus)),
                    step_s=self.step_s[-steps_run:],
                )
                self.obs.on_flags(self.epoch, self.step_i, flags)
            if self.cfg.policy == "adaptive":
                self.alloc = self.ctl.observe(t_s, t_c=t_c)
                if int(np.max(self.alloc)) > self.w_max:
                    # allocation outgrew the step buffers: rebuild with a
                    # deeper w_max instead of tripping the host check
                    self._log(f"[capacity] allocation {self.alloc.tolist()} > w_max={self.w_max}; rebuilding")
                    self._rebuild()
        else:
            # a resume landed mid-epoch: the pre-restart wall time is gone,
            # so skip ONE controller update rather than feed a truncated
            # measurement, and drop the partial accumulation so it cannot
            # bleed into the next epoch's reading
            self.timing.reset()
        self.epoch += 1
        self.agg_index = 0
        self._timing_from_agg = 0

    def _mesh_summary(self) -> dict:
        """The mesh the step ran on, as built (not as requested): a fold of
        several ranks onto one device shows here as a 1x1 shape."""
        devs = list(self.mesh.devices.flat)
        return {
            "shape": list(self.mesh.devices.shape),
            "axes": list(self.mesh.axis_names),
            "devices": len({d.id for d in devs}),
            "platform": devs[0].platform,
        }

    def _epoch_summary(self) -> dict:
        times = [e["epoch_s"] for e in self.epoch_log]
        return {
            "epochs": len(times),
            "total_s": float(np.sum(times)) if times else 0.0,
            # None (-> json null), not NaN: the result is advertised as
            # --json-out and NaN is not strict JSON
            "first_epoch_s": times[0] if times else None,
            "last_epoch_s": times[-1] if times else None,
            "improvement": float(1.0 - times[-1] / times[0]) if len(times) > 1 and times[0] > 0 else 0.0,
        }

    def _log(self, msg: str) -> None:
        if self.cfg.verbose:
            print(msg, flush=True)
