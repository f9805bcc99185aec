"""End-to-end system tests: training converges, allocation adapts,
checkpoint/restart resumes exactly, serving decodes."""

import json
import logging

import jax
import numpy as np
import pytest

from repro.launch import train as train_cli


def test_static_policy_without_ratio_is_an_error():
    """Regression: --policy static with no --static-ratio silently fell
    through to the controller's equal allocation."""
    with pytest.raises(SystemExit):
        train_cli.parse_args(["--arch", "smollm-360m", "--policy", "static"])
    # the combination that works
    args = train_cli.parse_args(
        ["--arch", "smollm-360m", "--policy", "static", "--static-ratio", "6,4"]
    )
    assert args.static_ratio == "6,4"


def test_fsdp_gather_requires_while_mode_cli():
    with pytest.raises(SystemExit):
        train_cli.parse_args(["--arch", "smollm-360m", "--fsdp", "gather", "--mode", "masked"])


def test_bad_events_schedule_is_an_argparse_error():
    """A typo in --events must fail at parse time, not 24 steps into the run."""
    with pytest.raises(SystemExit):
        train_cli.parse_args(["--arch", "smollm-360m", "--events", "explode@8:1"])
    with pytest.raises(SystemExit):
        train_cli.parse_args(["--arch", "smollm-360m", "--events", "add@8:warp9"])
    args = train_cli.parse_args(
        ["--arch", "smollm-360m", "--events", "fail@8:3,add@16:v100,replace@24:0=v100"]
    )
    assert args.events


def test_driver_validates_config_without_the_cli():
    """The driver is the advertised programmatic entry point; the CLI's
    argparse guards must exist there too, with clear messages."""
    from repro.runtime.driver import DriverConfig, ElasticTrainer

    with pytest.raises(ValueError, match="static_ratio"):
        ElasticTrainer(DriverConfig(arch="smollm-360m", smoke=True, policy="static"))
    with pytest.raises(ValueError, match="while"):
        ElasticTrainer(DriverConfig(arch="smollm-360m", smoke=True, fsdp="gather"))
    with pytest.raises(ValueError, match="policy"):
        ElasticTrainer(DriverConfig(arch="smollm-360m", smoke=True, policy="chaotic"))
    # n_workers / hetero_gpus disagreement would silently train the wrong
    # worker count (the GPU list defines the fleet)
    with pytest.raises(ValueError, match="n_workers"):
        ElasticTrainer(
            DriverConfig(arch="smollm-360m", smoke=True, n_workers=8, hetero_gpus="v100,v100")
        )
    # a fleet GPU typo fails up front like an --events typo, not as a
    # KeyError from deep inside the build
    with pytest.raises(ValueError, match="unknown GPU"):
        ElasticTrainer(
            DriverConfig(arch="smollm-360m", smoke=True, n_workers=2, hetero_gpus="v100,rtx2080it")
        )
    # zero patience would make fail events silent no-ops (the detector loop
    # never ticks, nobody is declared dead)
    with pytest.raises(ValueError, match="heartbeat_patience"):
        ElasticTrainer(DriverConfig(arch="smollm-360m", smoke=True, heartbeat_patience=0))


@pytest.mark.slow
def test_elastic_fail_last_worker_is_a_clear_error():
    """Failing the only remaining worker must raise a clear event-time error,
    not a deep resize(0) traceback after writing the barrier checkpoint."""
    with pytest.raises(ValueError, match="last remaining worker"):
        train_cli.main(
            [
                "--arch", "smollm-360m", "--smoke", "--steps", "6",
                "--n-workers", "1", "--total-micro", "2", "--micro-bs", "1",
                "--seq", "16", "--events", "fail@2:0",
            ]
        )


@pytest.mark.slow
def test_equal_policy_survives_membership_events():
    """policy=equal is a statement about the allocation, not the fleet: a
    membership event must re-apply EQUAL over the new membership, not switch
    to the coordinator's speed-proportional plan forever."""
    res = train_cli.main(
        [
            "--arch", "smollm-360m", "--smoke", "--steps", "12",
            "--n-workers", "2", "--total-micro", "6", "--micro-bs", "1",
            "--seq", "16", "--policy", "equal",
            "--hetero-gpus", "v100,gtx1080ti", "--events", "add@6:v100",
        ]
    )
    assert res["n_workers"] == 3
    assert res["final_allocation"] == [2, 2, 2]
    for m in res["memberships"]:
        assert max(m["allocation"]) - min(m["allocation"]) <= 1


@pytest.mark.slow
def test_resume_with_different_policy_is_an_error(tmp_path):
    """Silently resuming an adaptive checkpoint under --policy static would
    train on an allocation the flags never requested."""
    common = [
        "--arch", "smollm-360m", "--smoke", "--n-workers", "2",
        "--total-micro", "4", "--micro-bs", "1", "--seq", "16",
        "--ckpt-dir", str(tmp_path / "ck"),
    ]
    train_cli.main(common + ["--steps", "3"])
    with pytest.raises(ValueError, match="policy"):
        train_cli.main(
            common + ["--steps", "6", "--resume", "--policy", "static", "--static-ratio", "3,1"]
        )
    # same for the timing mode: dropping --hetero-gpus on resume would flip
    # the controller onto measured wall-seconds while its restored log still
    # carries simulated speed units
    ck2 = str(tmp_path / "ck2")
    train_cli.main(
        common[:-2] + ["--ckpt-dir", ck2, "--steps", "3", "--hetero-gpus", "v100,gtx1080ti"]
    )
    with pytest.raises(ValueError, match="timing"):
        train_cli.main(common[:-2] + ["--ckpt-dir", ck2, "--steps", "6", "--resume"])
    # and for the data-defining flags: a different seed (or dataset size,
    # microbatching, ...) makes the restored epoch/agg position point into a
    # different sample order
    with pytest.raises(ValueError, match="data stream"):
        train_cli.main(common + ["--steps", "6", "--resume", "--seed", "7"])
    with pytest.raises(ValueError, match="data stream"):
        train_cli.main(common + ["--steps", "6", "--resume", "--steps-per-epoch", "2"])
    # a same-length but different initial fleet must not be silently
    # discarded in favour of the checkpointed one
    with pytest.raises(ValueError, match="data stream"):
        train_cli.main(
            common[:-2] + ["--ckpt-dir", ck2, "--steps", "6", "--resume",
                           "--hetero-gpus", "v100,v100"]
        )
    # the persisted event cursor indexes into the SCHEDULE: resuming with a
    # different one would mis-apply events
    with pytest.raises(ValueError, match="data stream"):
        train_cli.main(common + ["--steps", "6", "--resume", "--events", "add@5:v100"])


@pytest.mark.slow
def test_short_run_json_out_is_strict_json(tmp_path):
    """A run too short to complete an epoch must still emit strict JSON
    (null, not NaN) so non-Python consumers can parse --json-out."""
    out = tmp_path / "o.json"
    train_cli.main(
        [
            "--arch", "smollm-360m", "--smoke", "--steps", "2", "--n-workers", "2",
            "--total-micro", "4", "--micro-bs", "1", "--seq", "16",
            "--json-out", str(out),
        ]
    )

    def reject(const):
        raise ValueError(f"non-strict JSON constant {const}")

    data = json.loads(out.read_text(), parse_constant=reject)
    assert data["epoch_summary"]["first_epoch_s"] is None


@pytest.mark.slow
def test_resume_does_not_replay_data(tmp_path):
    """Satellite regression: --resume restarted epoch 0 / aggregation 0 and
    replayed the identical sample order after every restart.  A run killed
    mid-epoch must consume the epochs and aggregations the uninterrupted run
    would have — and (under deterministic measured timing) reproduce its
    losses exactly."""
    common = [
        "--arch", "smollm-360m", "--smoke", "--n-workers", "4",
        "--total-micro", "8", "--micro-bs", "1", "--seq", "16",
        "--steps-per-epoch", "3",  # 2N=16 steps cross five epoch boundaries
    ]
    full = train_cli.main(common + ["--steps", "16"])
    ck = str(tmp_path / "ck")
    # killed at step 8 = epoch 2, aggregation 2 (mid-epoch)
    partial = train_cli.main(common + ["--steps", "8", "--ckpt-dir", ck, "--ckpt-every", "5"])
    assert (partial["epoch"], partial["agg_index"]) == (2, 2)
    resumed = train_cli.main(common + ["--steps", "16", "--ckpt-dir", ck, "--resume"])
    assert resumed["steps"] == 16
    # same data position as the uninterrupted run: no epoch was replayed
    assert (resumed["epoch"], resumed["agg_index"]) == (full["epoch"], full["agg_index"])
    # same data -> same trajectory (measured timing is deterministic here)
    np.testing.assert_allclose(resumed["last_loss"], full["last_loss"], rtol=1e-6)
    # and no phantom timing entries for epochs this process never stepped
    assert all(e["steps"] > 0 for e in resumed["epoch_log"])


@pytest.mark.slow
def test_resume_at_epoch_boundary_logs_no_phantom_epoch(tmp_path):
    """A checkpoint can land exactly on an epoch's last aggregation (saved
    after the step, before the epoch-end bookkeeping).  Resuming from it must
    not log a 0-step epoch with a full epoch_s (simulated timing would
    happily invent one, inflating epoch_summary and the BENCH curve)."""
    from repro.checkpoint import CheckpointManager
    from repro.runtime.driver import DriverConfig, ElasticTrainer

    ck = str(tmp_path / "ck")
    common = [
        "--arch", "smollm-360m", "--smoke", "--n-workers", "2",
        "--total-micro", "4", "--micro-bs", "1", "--seq", "16",
        "--steps-per-epoch", "3", "--hetero-gpus", "v100,gtx1080ti",
        "--ckpt-dir", ck, "--ckpt-every", "3",
    ]
    # emulate the kill window: run exactly one epoch's steps so the periodic
    # save at step 3 (epoch 0, agg 3) is the LAST write — the process dies
    # before _finish_epoch and before any terminal save
    tr = ElasticTrainer(
        DriverConfig(
            arch="smollm-360m", smoke=True, steps=3, n_workers=2, total_micro=4,
            micro_bs=1, seq=16, steps_per_epoch=3, hetero_gpus="v100,gtx1080ti",
            ckpt_dir=ck, ckpt_every=3, verbose=False,
        )
    )
    tr._run_epoch()  # stops at the step budget, inside the epoch boundary window
    _, _, meta = CheckpointManager(ck).restore(tr.state)
    assert (meta["epoch"], meta["agg_index"]) == (0, 3)  # the boundary checkpoint
    resumed = train_cli.main(common + ["--steps", "9", "--resume"])
    assert resumed["steps"] == 9
    assert all(e["steps"] > 0 for e in resumed["epoch_log"])
    # the boundary epoch's controller update still happened (simulated times
    # cover the whole epoch), so adaptation continuity is preserved
    alloc = resumed["final_allocation"]
    assert sum(alloc) == 4
    assert alloc[0] > alloc[1]  # v100 (2.1x) out-ranks the 1080ti


@pytest.mark.slow
def test_elastic_events_end_to_end(tmp_path):
    """The paper's fig. 11 runtime: one fail, one add, one replace, scripted
    through the driver on masked mode with simulated heterogeneous speeds."""
    res = train_cli.main(
        [
            "--arch", "smollm-360m", "--smoke", "--steps", "28",
            "--n-workers", "4", "--total-micro", "12", "--micro-bs", "1",
            "--seq", "16", "--steps-per-epoch", "4",
            "--hetero-gpus", "v100,rtx2080ti,rtx2080ti,gtx1080ti",
            "--events", "fail@8:3,add@16:gtx1080ti,replace@24:1=v100",
            "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "10",
            "--json-out", str(tmp_path / "out.json"),
        ]
    )
    assert res["steps"] == 28
    assert res["events_applied"] == 3 and res["events_pending"] == 0
    # losses stay finite across every rebuild, and training still learns
    assert np.isfinite(res["first_loss"]) and np.isfinite(res["last_loss"])
    assert res["last_loss"] < res["first_loss"]
    # membership: 4 -> fail -> 3 -> add -> 4, replace keeps 4
    sizes = [len(m["gpus"]) for m in res["memberships"]]
    assert sizes == [3, 4, 4]
    assert res["gpus"] == ["v100", "v100", "rtx2080ti", "gtx1080ti"]
    # allocation always sums to C (eq. 4: the optimizer schedule never changes)
    for m in res["memberships"]:
        assert sum(m["allocation"]) == 12
    for e in res["epoch_log"]:
        assert sum(e["alloc"]) == 12
    alloc = np.array(res["final_allocation"])
    assert alloc.sum() == 12
    # carried speeds: the two v100s (21) out-rank the 2080ti (14.5) and the
    # 1080ti (10) in the final membership's allocation
    assert alloc[0] >= alloc[2] >= alloc[3]
    assert alloc[1] >= alloc[2]
    assert alloc.max() > alloc.min()  # genuinely heterogeneous, not equal


@pytest.mark.slow
def test_elastic_fail_through_detector_carries_speeds(tmp_path):
    """A fail event goes through the FailureDetector (missed heartbeats ->
    declared dead) and the survivors keep their measured speeds: with the
    slowest card gone, the v100 must keep the largest share."""
    res = train_cli.main(
        [
            "--arch", "smollm-360m", "--smoke", "--steps", "16",
            "--n-workers", "3", "--total-micro", "12", "--micro-bs", "1",
            "--seq", "16", "--steps-per-epoch", "4",
            "--hetero-gpus", "v100,rtx2080ti,gtx1080ti",
            "--events", "fail@8:2",
        ]
    )
    assert res["n_workers"] == 2
    assert res["gpus"] == ["v100", "rtx2080ti"]
    alloc = np.array(res["final_allocation"])
    assert alloc.sum() == 12
    assert alloc[0] > alloc[1]  # v100 (2.1x) keeps the bigger share


@pytest.mark.slow
def test_elastic_benchmark_scenario_fig11_shape(tmp_path):
    """benchmarks/run.py --scenario elastic: per-epoch time must DROP after
    the weak->strong replacement (fig. 11's headline curve)."""
    from benchmarks.run import run_elastic_scenario

    out = str(tmp_path / "bench_elastic.json")
    bench = run_elastic_scenario(out, steps=32)
    assert bench["pre_mean_s"] > bench["post_mean_s"]
    assert bench["improvement"] > 0.05
    with open(out) as f:
        on_disk = json.load(f)
    assert on_disk["scenario"] == "elastic"
    assert on_disk["improvement"] == bench["improvement"]


@pytest.mark.slow
def test_static_resume_preserves_allocation(tmp_path):
    """Regression: --resume restored the controller and overwrote the static
    allocation with the controller's equal split."""
    common = [
        "--arch", "smollm-360m", "--smoke", "--n-workers", "2",
        "--total-micro", "4", "--micro-bs", "1", "--seq", "16",
        "--policy", "static", "--static-ratio", "3,1",
        "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "3",
    ]
    first = train_cli.main(common + ["--steps", "3"])
    assert first["final_allocation"] == [3, 1]
    resumed = train_cli.main(common + ["--steps", "6", "--resume"])
    assert resumed["final_allocation"] == [3, 1]


@pytest.mark.slow
def test_train_cli_while_gather_mode(tmp_path):
    """End-to-end CLI smoke of the ZeRO path: --mode while --fsdp gather."""
    res = train_cli.main(
        [
            "--arch", "smollm-360m", "--smoke", "--steps", "6",
            "--n-workers", "2", "--total-micro", "4", "--micro-bs", "1",
            "--seq", "16", "--mode", "while", "--fsdp", "gather",
            "--json-out", str(tmp_path / "out.json"),
        ]
    )
    assert res["steps"] == 6
    assert res["last_loss"] == res["last_loss"]  # finite, no NaN
    assert res["last_loss"] < res["first_loss"] * 1.5  # sane magnitude


@pytest.mark.slow
def test_end_to_end_adaptive_training_loss_drops(tmp_path):
    """Full loop: synthetic data -> hetero step -> controller -> loss drops and
    the allocation converges toward the simulated speed ratio."""
    res = train_cli.main(
        [
            "--arch", "smollm-360m", "--smoke", "--steps", "30",
            "--n-workers", "4", "--total-micro", "8", "--micro-bs", "2",
            "--seq", "32", "--steps-per-epoch", "3",
            "--hetero-gpus", "v100,rtx2080ti,rtx2080ti,gtx1080ti",
            "--json-out", str(tmp_path / "out.json"),
        ]
    )
    assert res["last_loss"] < res["first_loss"]  # learning
    alloc = np.array(res["final_allocation"])
    assert alloc.sum() == 8
    # v100 (2.1x) gets the most, 1080ti (1.0x) the least
    assert alloc[0] == alloc.max()
    assert alloc[3] == alloc.min()


@pytest.mark.slow
def test_checkpoint_restart_resumes_exactly(tmp_path):
    """Fault-tolerance: kill training at step 20, resume, final state matches
    an uninterrupted run (same data order, same controller state)."""
    common = [
        "--arch", "smollm-360m", "--smoke", "--n-workers", "2",
        "--total-micro", "4", "--micro-bs", "2", "--seq", "32",
        "--hetero-gpus", "v100,gtx1080ti", "--seed", "3",
    ]
    full = train_cli.main(common + ["--steps", "30"])

    ck = str(tmp_path / "ck")
    train_cli.main(common + ["--steps", "20", "--ckpt-dir", ck, "--ckpt-every", "10"])
    resumed = train_cli.main(
        common + ["--steps", "30", "--ckpt-dir", ck, "--ckpt-every", "10", "--resume"]
    )
    assert resumed["steps"] == 30
    np.testing.assert_allclose(resumed["last_loss"], full["last_loss"], rtol=0.05)


@pytest.mark.slow
def test_serve_cli_decodes():
    from repro.launch import serve as serve_cli

    res = serve_cli.main(
        [
            "--arch", "rwkv6-1.6b", "--smoke", "--slots", "2", "--requests", "4",
            "--prompt-lens", "4,8", "--gen-lens", "4,8", "--rate", "0.5",
        ]
    )
    assert res["mode"] == "continuous"
    assert res["completed"] == 4
    assert res["gen_tokens"] > 0 and res["throughput_tok_per_s"] > 0
    assert 0 < res["slot_utilization"] <= 1


def test_driver_result_names_the_folded_mesh():
    """More ranks than devices fold onto a 1x1 mesh, and the result says so."""
    result = train_cli.main(
        ["--arch", "smollm-360m", "--smoke", "--n-workers", "2", "--steps", "2",
         "--total-micro", "4", "--micro-bs", "1", "--seq", "16"]
    )
    assert result["mesh"] == {"shape": [1, 1], "axes": ["data", "model"], "devices": 1, "platform": "cpu"}
    assert len(result["losses"]) == len(result["step_s"]) == 2


def test_train_step_compiles_once(caplog):
    """The driver places the state where the step returns it, so every step
    after the first reuses the first step's executable."""
    with caplog.at_level(logging.WARNING, logger="jax"), jax.log_compiles(True):
        result = train_cli.main(
            ["--arch", "smollm-360m", "--smoke", "--n-workers", "2", "--steps", "3",
             "--total-micro", "4", "--micro-bs", "1", "--seq", "16"]
        )
    compiles = [r for r in caplog.records if r.getMessage().startswith("Compiling jit(step)")]
    assert len(compiles) == 1
    assert len(result["grad_norms"]) == 3 and all(g > 0 for g in result["grad_norms"])
