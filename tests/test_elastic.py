"""Elastic recovery: cluster-consensus resume, checkpoint replication, and
topology-change restarts (docs/fault_tolerance.md "Replication & elastic
resume").

Fast tests run in-process or drive small subprocesses (the replication
kill-point campaign and the dp-change parity checks, fault_save_script.py
style). The end-to-end host-loss acceptance test forks real jax.distributed
clusters and is marked slow, like every _spawn_cluster test.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.parallelism_config import ParallelismConfig
from accelerate_tpu.test_utils.training import (
    RegressionModel,
    make_regression_data,
    regression_loss,
)
from accelerate_tpu.utils.dataclasses import ReplicationConfig
from accelerate_tpu.utils.fault import (
    CheckpointDivergedError,
    CheckpointNotFoundError,
    CheckpointTopologyError,
    ReplicaUnavailableError,
)

SCRIPTS = os.path.join(
    os.path.dirname(__file__), "..", "accelerate_tpu", "test_utils", "scripts"
)
ELASTIC_SCRIPT = os.path.join(SCRIPTS, "elastic_recovery_script.py")


def _subprocess_env(device_count=8, replica=None, sync=True):
    env = dict(os.environ)
    env.pop("ACCELERATE_TPU_FAULT_INJECT", None)
    env.pop("ACCELERATE_REPLICATION_TARGET", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={device_count}"
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    if replica is not None:
        env["ACCELERATE_REPLICATION_TARGET"] = str(replica)
        if sync:
            env["ACCELERATE_REPLICATION_SYNC"] = "1"
    return env


def _fresh(tmp_path, **kwargs):
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        project_dir=str(tmp_path),
        **kwargs,
    )
    acc.project_configuration.automatic_checkpoint_naming = True
    return acc


def _prepared(acc):
    model = RegressionModel()
    optimizer = optax.adam(0.1)
    data = make_regression_data(32)
    loader = acc.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, optimizer = acc.prepare(model, optimizer)
    return model, optimizer, loader


def _one_step(acc, model, optimizer, batch):
    with acc.accumulate(model):
        acc.backward(regression_loss, batch)
        optimizer.step()
        optimizer.zero_grad()


def _params(model) -> np.ndarray:
    import jax

    return np.concatenate(
        [np.asarray(jax.device_get(l)).ravel()
         for l in jax.tree_util.tree_leaves(model.params)]
    )


def _sync_config(tmp_path, **kwargs) -> ReplicationConfig:
    return ReplicationConfig(
        target=str(tmp_path / "replica"), async_replicate=False, **kwargs
    )


# ------------------------------------------------------------- configuration
def test_replication_config_validation():
    with pytest.raises(ValueError):
        ReplicationConfig(target="")
    with pytest.raises(ValueError):
        ReplicationConfig(target="/r", copies=0)
    with pytest.raises(ValueError):
        ReplicationConfig(target="/r", max_retries=-1)
    with pytest.raises(ValueError):
        ReplicationConfig(target="/r", retry_backoff_s=-0.1)
    with pytest.raises(ValueError):
        ReplicationConfig(target="/r", verify="bogus")
    with pytest.raises(ValueError):
        ReplicationConfig(target="/r", keep=0)
    ReplicationConfig(target="/r", copies=2, keep=3)


# ------------------------------------------------------------------- digests
def test_manifest_digest_rng_and_time_invariant():
    from accelerate_tpu.elastic import manifest_digest

    base = {
        "format": 1,
        "step": 7,
        "time": 1111.0,
        "files": {
            "model/a.bin": {"size": 10, "crc32": "aa"},
            "sampler.json": {"size": 5, "crc32": "bb"},
            "random_states_0.pkl": {"size": 99, "crc32": "cc"},
        },
    }
    other = json.loads(json.dumps(base))
    other["time"] = 2222.0
    # per-rank RNG files legitimately differ across hosts — not divergence
    other["files"]["random_states_3.pkl"] = {"size": 1, "crc32": "zz"}
    del other["files"]["random_states_0.pkl"]
    assert manifest_digest(base) == manifest_digest(other)

    other["files"]["model/a.bin"]["crc32"] = "XX"
    assert manifest_digest(base) != manifest_digest(other)
    other["files"]["model/a.bin"]["crc32"] = "aa"
    other["step"] = 8
    assert manifest_digest(base) != manifest_digest(other)


# ------------------------------------------------------- replication (mirror)
def test_sync_replication_mirrors_checkpoint(tmp_path):
    from accelerate_tpu.checkpointing import verify_checkpoint
    from accelerate_tpu.elastic import checkpoint_digest

    acc = _fresh(tmp_path / "proj", replication_config=_sync_config(tmp_path))
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))
    acc.save_state()

    local = os.path.join(str(tmp_path / "proj"), "checkpoints", "checkpoint_0")
    replica = str(tmp_path / "replica" / "r0" / "checkpoint_0")
    assert os.path.isfile(os.path.join(replica, "COMMITTED"))
    verify_checkpoint(replica, level="checksum")
    assert checkpoint_digest(replica) == checkpoint_digest(local)
    # no staging/parking leftovers after a clean mirror
    assert not os.path.exists(replica + ".tmp")
    assert not os.path.exists(replica + ".old")


def test_replication_multiple_copies_and_retention(tmp_path):
    acc = _fresh(
        tmp_path / "proj",
        replication_config=_sync_config(tmp_path, copies=2, keep=1),
    )
    model, optimizer, loader = _prepared(acc)
    batch = next(iter(loader))
    for _ in range(2):  # checkpoint_0, checkpoint_1
        _one_step(acc, model, optimizer, batch)
        acc.save_state()
    for slot in ("r0", "r1"):
        root = tmp_path / "replica" / slot
        assert not (root / "checkpoint_0").exists()  # keep=1 GC'd it
        assert (root / "checkpoint_1" / "COMMITTED").is_file()


def test_async_replication_drained_by_end_training(tmp_path):
    acc = _fresh(
        tmp_path / "proj",
        replication_config=ReplicationConfig(target=str(tmp_path / "replica")),
    )
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))
    acc.save_state()
    acc.end_training()  # joins the replicator like wait_for_async_saves
    replica = tmp_path / "replica" / "r0" / "checkpoint_0"
    assert (replica / "COMMITTED").is_file()


def test_replicator_backlog_drops_oldest_latest_wins():
    from accelerate_tpu.elastic import CheckpointReplicator

    rep = CheckpointReplicator(ReplicationConfig(target="/nowhere"))
    gate = threading.Event()
    mirrored = []

    def _slow_mirror(src):
        gate.wait(10)
        mirrored.append(src)

    rep._mirror_with_retry = _slow_mirror
    for name in ("c0", "c1", "c2", "c3"):
        rep.submit(name)
    assert rep.pending <= 3  # one in flight + at most _MAX_PENDING queued
    gate.set()
    rep.drain(timeout=10)
    rep.close()
    # the newest submission is never the one dropped
    assert mirrored[-1] == "c3"
    assert len(mirrored) <= 3


def test_sync_replication_failure_raises_after_retries(tmp_path, monkeypatch):
    import accelerate_tpu.elastic as elastic_mod

    acc = _fresh(
        tmp_path / "proj",
        replication_config=_sync_config(tmp_path, max_retries=1, retry_backoff_s=0.0),
    )
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))

    attempts = []

    def _boom(src, dst, config):
        attempts.append(dst)
        raise OSError("target volume gone")

    monkeypatch.setattr(elastic_mod, "_mirror_one", _boom)
    with pytest.raises(OSError, match="target volume gone"):
        acc.save_state()
    assert len(attempts) == 2  # initial + max_retries
    # the LOCAL checkpoint is durable regardless of replication failure
    from accelerate_tpu.checkpointing import is_checkpoint_committed

    assert is_checkpoint_committed(
        os.path.join(str(tmp_path / "proj"), "checkpoints", "checkpoint_0")
    )


def test_degraded_slot_does_not_cost_healthy_slots_their_copy(tmp_path, monkeypatch):
    """Regression: a persistently failing r0 must not skip r1's mirror —
    every copy slot is attempted independently and the failure is raised
    (aggregated) only after all slots were tried."""
    import accelerate_tpu.elastic as elastic_mod
    from accelerate_tpu.checkpointing import is_checkpoint_committed
    from accelerate_tpu.utils.fault import CheckpointError

    acc = _fresh(
        tmp_path / "proj",
        replication_config=_sync_config(
            tmp_path, copies=2, max_retries=0, retry_backoff_s=0.0
        ),
    )
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))

    real_mirror = elastic_mod._mirror_one

    def _r0_down(src, dst, config):
        if f"{os.sep}r0{os.sep}" in dst:
            raise OSError("r0 volume gone")
        real_mirror(src, dst, config)

    monkeypatch.setattr(elastic_mod, "_mirror_one", _r0_down)
    with pytest.raises(CheckpointError, match=r"1/2 copy slot"):
        acc.save_state()
    # the healthy slot got its fresh copy despite r0's failure
    assert is_checkpoint_committed(
        str(tmp_path / "replica" / "r1" / "checkpoint_0")
    )
    assert not os.path.isdir(tmp_path / "replica" / "r0" / "checkpoint_0")


# ------------------------------------------------------------ replica restore
def test_resume_restores_bit_identical_from_replica_after_tree_wipe(tmp_path):
    proj = tmp_path / "proj"
    acc = _fresh(proj, replication_config=_sync_config(tmp_path))
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))
    acc.save_state()
    saved = _params(model)

    shutil.rmtree(proj / "checkpoints")  # the host's disk is gone

    acc2 = _fresh(proj, replication_config=_sync_config(tmp_path))
    model2, optimizer2, loader2 = _prepared(acc2)
    assert acc2.resume_from_latest() is True
    np.testing.assert_array_equal(_params(model2), saved)
    # the replica was copied back as a committed local checkpoint
    assert (proj / "checkpoints" / "checkpoint_0" / "COMMITTED").is_file()


def test_first_launch_without_replicas_still_returns_false(tmp_path):
    acc = _fresh(tmp_path / "proj", replication_config=_sync_config(tmp_path))
    _prepared(acc)
    assert acc.resume_from_latest() is False


def test_corrupt_replica_skipped_for_second_copy(tmp_path):
    proj = tmp_path / "proj"
    acc = _fresh(proj, replication_config=_sync_config(tmp_path, copies=2))
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))
    acc.save_state()
    saved = _params(model)

    # bit-flip one payload file in replica slot r0 (same size: only the
    # checksum proof can catch it)
    victim = tmp_path / "replica" / "r0" / "checkpoint_0" / "sampler.json"
    victim.write_bytes(b"X" * len(victim.read_bytes()))
    shutil.rmtree(proj / "checkpoints")

    acc2 = _fresh(proj, replication_config=_sync_config(tmp_path, copies=2))
    model2, _opt2, _loader2 = _prepared(acc2)
    assert acc2.resume_from_latest() is True  # r0 refused, r1 restored
    np.testing.assert_array_equal(_params(model2), saved)


def test_all_replicas_corrupt_raises_checksum_refusal(tmp_path):
    proj = tmp_path / "proj"
    acc = _fresh(proj, replication_config=_sync_config(tmp_path))
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))
    acc.save_state()

    victim = tmp_path / "replica" / "r0" / "checkpoint_0" / "sampler.json"
    victim.write_bytes(b"X" * len(victim.read_bytes()))
    shutil.rmtree(proj / "checkpoints")

    acc2 = _fresh(proj, replication_config=_sync_config(tmp_path))
    _prepared(acc2)
    with pytest.raises(ReplicaUnavailableError, match="checkpoint"):
        acc2.resume_from_latest()


def test_corrupt_local_checkpoint_healed_from_replica(tmp_path):
    proj = tmp_path / "proj"
    acc = _fresh(proj, replication_config=_sync_config(tmp_path))
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))
    ckpt = acc.save_state()
    saved = _params(model)

    victim = os.path.join(ckpt, "sampler.json")
    size = os.path.getsize(victim)
    with open(victim, "wb") as f:
        f.write(b"X" * size)

    acc.load_state(ckpt, verify="checksum")  # parks the damage, pulls replica
    np.testing.assert_array_equal(_params(model), saved)
    assert os.path.isdir(ckpt + ".corrupt")
    from accelerate_tpu.checkpointing import verify_checkpoint

    verify_checkpoint(ckpt, level="checksum")


def test_restore_from_replica_without_any_replica_raises_not_found(tmp_path):
    from accelerate_tpu.elastic import restore_from_replica

    with pytest.raises(CheckpointNotFoundError):
        restore_from_replica(_sync_config(tmp_path), str(tmp_path / "local"))


# ------------------------------------------------------------- topology gate
def test_topology_mismatch_raises_typed_error_and_elastic_reshards(tmp_path):
    from accelerate_tpu.checkpointing import read_commit_manifest

    acc = _fresh(tmp_path / "proj")
    model, optimizer, loader = _prepared(acc)
    _one_step(acc, model, optimizer, next(iter(loader)))
    ckpt = acc.save_state()

    manifest = read_commit_manifest(ckpt)
    topo = manifest["topology"]
    assert topo["num_processes"] == 1
    assert topo["num_devices"] == 8
    assert topo["mesh_axes"].get("dp_shard") == 8

    # rewrite the manifest as if the checkpoint came from a 4-process world
    manifest["topology"]["num_processes"] = 4
    with open(os.path.join(ckpt, "COMMITTED"), "w") as f:
        json.dump(manifest, f)

    with pytest.raises(CheckpointTopologyError) as err:
        acc.load_state(ckpt)
    msg = str(err.value)
    assert "num_processes 4 (saved) != 1 (live)" in msg
    assert "elastic=True" in msg

    acc.load_state(ckpt, elastic=True)  # explicit opt-in reshards instead


def test_pre_elastic_manifest_topology_fallback():
    from accelerate_tpu.elastic import manifest_topology

    assert manifest_topology({"num_processes": 2}) == {"num_processes": 2}
    assert manifest_topology({"topology": {"num_processes": 3}}) == {
        "num_processes": 3
    }
    assert manifest_topology({}) == {}


# -------------------------------------------------------------- sampler remap
def test_remap_sampler_state_conserves_samples():
    from accelerate_tpu.elastic import remap_sampler_state

    # same global batch → exact identity (the topology-change convention)
    sd = {"position": 4, "skip_batches": 2, "total_batch_size": 16}
    assert remap_sampler_state(sd, 16, 16) is sd

    # 4 batches x 16 samples = 64 samples = 8 new batches of 8
    out = remap_sampler_state(sd, 16, 8)
    assert out["position"] == 8 and out["skip_batches"] == 4

    # growing the global batch: floor → a few samples replay, never skip
    out = remap_sampler_state({"position": 3}, 16, 12)
    assert out["position"] == 4  # 48 samples // 12

    out = remap_sampler_state({"position": 5}, 8, 32)
    assert out["position"] == 1  # 40 samples // 32 → 8 samples replayed


# ------------------------------------------------------------------ consensus
def test_consensus_laggard_resolves_to_common_index(tmp_path):
    from accelerate_tpu.elastic import _consensus_from_views

    views = [{0: "a", 1: "b"}, {0: "a", 1: "b"}, {0: "a"}]  # rank 2 lags
    res = _consensus_from_views(views, str(tmp_path), rank=0)
    assert res.index == 0 and res.digest == "a"
    assert res.local_path.endswith("checkpoint_0")


def test_consensus_empty_host_fetches_from_replica(tmp_path):
    from accelerate_tpu.elastic import _consensus_from_views

    views = [{}, {1: "d"}]  # rank 0's disk was wiped
    res0 = _consensus_from_views(views, str(tmp_path), rank=0)
    assert res0.index == 1 and res0.local_path is None
    res1 = _consensus_from_views(views, str(tmp_path), rank=1)
    assert res1.local_path.endswith("checkpoint_1")

    assert _consensus_from_views([{}, {}], str(tmp_path), rank=0) is None


def test_consensus_missing_ranks_is_identical_on_every_rank(tmp_path):
    """missing_ranks drives the collective fetch decision — derived from the
    gathered views, it must be the same tuple on every rank (the fetch path
    contains collectives, so holders and non-holders must branch together)."""
    from accelerate_tpu.elastic import _consensus_from_views

    views = [{0: "a", 1: "b"}, {0: "a"}, {}]  # rank 1 lags, rank 2 wiped
    for rank in range(3):
        res = _consensus_from_views(views, str(tmp_path), rank=rank)
        assert res.index == 0
        assert res.missing_ranks == (2,)
    full = [{1: "b"}, {1: "b"}]
    for rank in range(2):
        assert _consensus_from_views(full, str(tmp_path), rank=rank).missing_ranks == ()


def test_consensus_digest_mismatch_is_divergence(tmp_path):
    from accelerate_tpu.elastic import _consensus_from_views

    with pytest.raises(CheckpointDivergedError, match="DIFFERENT content"):
        _consensus_from_views([{1: "x"}, {1: "y"}], str(tmp_path), rank=0)
    with pytest.raises(CheckpointDivergedError, match="no committed checkpoint"):
        _consensus_from_views([{0: "a"}, {1: "b"}], str(tmp_path), rank=0)


def test_resolve_consensus_single_process(tmp_path):
    from accelerate_tpu.elastic import resolve_consensus_checkpoint

    proj = tmp_path / "proj"
    acc = _fresh(proj)
    model, optimizer, loader = _prepared(acc)
    batch = next(iter(loader))
    base = os.path.join(str(proj), "checkpoints")
    assert resolve_consensus_checkpoint(base) is None
    for _ in range(2):
        _one_step(acc, model, optimizer, batch)
        acc.save_state()
    res = resolve_consensus_checkpoint(base)
    assert res.index == 1
    assert res.local_path == os.path.join(base, "checkpoint_1")


# ---------------------------------------------------------- launch supervisor
def test_apply_elastic_topology_reexports_env(tmp_path, capsys):
    from accelerate_tpu.commands.launch import _apply_elastic_topology

    topo = tmp_path / "topology.json"
    topo.write_text(json.dumps({
        "num_processes": 2,
        "process_id": 0,
        "coordinator_address": "10.0.0.5:1234",
    }))
    env = {"ACCELERATE_ELASTIC_TOPOLOGY_FILE": str(topo),
           "ACCELERATE_NUM_PROCESSES": "4"}
    _apply_elastic_topology(env, attempt=1)
    assert env["ACCELERATE_NUM_PROCESSES"] == "2"
    assert env["ACCELERATE_PROCESS_ID"] == "0"
    assert env["ACCELERATE_COORDINATOR_ADDRESS"] == "10.0.0.5:1234"
    assert "elastic relaunch" in capsys.readouterr().err

    # no topology file → a fixed-topology restart is untouched
    env2 = {"ACCELERATE_NUM_PROCESSES": "4"}
    _apply_elastic_topology(env2, attempt=1)
    assert env2 == {"ACCELERATE_NUM_PROCESSES": "4"}


# ----------------------------------------------- replication kill-point runs
def _run_script(env, *argv, timeout=300):
    return subprocess.run(
        [sys.executable, ELASTIC_SCRIPT, *argv],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_kill_between_commit_and_mirror(tmp_path):
    """Die after the local commit but before any replica byte is written:
    the replica set simply lacks checkpoint_1. With the local tree intact
    the resume loads local checkpoint_1; with the local tree wiped, the
    replica's checkpoint_0 restores bit-identically."""
    project = str(tmp_path / "proj")
    replica = tmp_path / "replica"
    ref = str(tmp_path / "ref")
    env = _subprocess_env(replica=replica)

    train = _run_script(
        env, "--phase", "train", "--project_dir", project,
        "--ref_out", ref, "--fault", "before_replicate:kill",
    )
    assert train.returncode == -signal.SIGKILL, (
        f"rc={train.returncode}\n{train.stdout}\n{train.stderr}"
    )
    assert "committed checkpoint_0" in train.stdout
    assert (replica / "r0" / "checkpoint_0" / "COMMITTED").is_file()
    assert not (replica / "r0" / "checkpoint_1").exists()

    # local tree intact: checkpoint_1 committed locally, loads fine
    got = str(tmp_path / "got.npy")
    verify = _run_script(
        env, "--phase", "verify", "--project_dir", project, "--ref_out", got,
    )
    assert verify.returncode == 0, f"{verify.stdout}\n{verify.stderr}"
    assert "resumed=True" in verify.stdout
    np.testing.assert_array_equal(np.load(ref + ".step2.npy"), np.load(got))

    # local tree wiped: only the replica's checkpoint_0 exists anywhere
    shutil.rmtree(os.path.join(project, "checkpoints"))
    verify2 = _run_script(
        env, "--phase", "verify", "--project_dir", project, "--ref_out", got,
    )
    assert verify2.returncode == 0, f"{verify2.stdout}\n{verify2.stderr}"
    assert "resumed=True" in verify2.stdout
    np.testing.assert_array_equal(np.load(ref + ".step1.npy"), np.load(got))


def test_kill_mid_mirror_leaves_uncommitted_replica(tmp_path):
    """Die between file copies into replica staging: the half-mirrored tree
    is an uncommitted ``.tmp`` the restore path never considers — a wiped
    host restores checkpoint_0's complete replica instead."""
    project = str(tmp_path / "proj")
    replica = tmp_path / "replica"
    ref = str(tmp_path / "ref")
    env = _subprocess_env(replica=replica)

    train = _run_script(
        env, "--phase", "train", "--project_dir", project,
        "--ref_out", ref, "--fault", "during_replicate:kill",
    )
    assert train.returncode == -signal.SIGKILL, (
        f"rc={train.returncode}\n{train.stdout}\n{train.stderr}"
    )
    assert "committed checkpoint_0" in train.stdout
    root = replica / "r0"
    assert (root / "checkpoint_0" / "COMMITTED").is_file()
    # checkpoint_1 died mid-copy: staging only, never a COMMITTED marker
    assert not (root / "checkpoint_1" / "COMMITTED").exists()
    assert (root / "checkpoint_1.tmp").is_dir()

    shutil.rmtree(os.path.join(project, "checkpoints"))
    got = str(tmp_path / "got.npy")
    verify = _run_script(
        env, "--phase", "verify", "--project_dir", project, "--ref_out", got,
    )
    assert verify.returncode == 0, f"{verify.stdout}\n{verify.stderr}"
    assert "resumed=True" in verify.stdout
    np.testing.assert_array_equal(np.load(ref + ".step1.npy"), np.load(got))


# ----------------------------------------------------- elastic dp-change runs
@pytest.fixture(scope="module")
def dp8_run(tmp_path_factory):
    """One uninterrupted dp=8 run: 5 steps, checkpoint after step 2, per-step
    losses + final params/moments. Shared by the dp=4 and dp=2 resumes (they
    only read the checkpoint)."""
    root = tmp_path_factory.mktemp("dp8")
    project = str(root / "proj")
    paths = {
        "project": project,
        "losses": str(root / "losses.npy"),
        "params": str(root / "params.npy"),
    }
    run = _run_script(
        _subprocess_env(device_count=8),
        "--phase", "parity", "--project_dir", project,
        "--ref_out", paths["params"], "--losses_out", paths["losses"],
        "--steps", "5", "--save_at", "2",
    )
    assert run.returncode == 0, f"{run.stdout}\n{run.stderr}"
    return paths


@pytest.mark.parametrize("dp", [4, 2])
def test_elastic_resume_at_smaller_dp_matches_trajectory(tmp_path, dp, dp8_run):
    """The dp-change parity criterion: resume the dp=8 checkpoint on a
    dp={4,2} mesh with elastic=True (same global batch) — the post-resume
    loss trajectory and the adam moments match the uninterrupted run."""
    losses = str(tmp_path / "losses.npy")
    params = str(tmp_path / "params.npy")
    run = _run_script(
        _subprocess_env(device_count=dp),
        "--phase", "parity-resume", "--project_dir", dp8_run["project"],
        "--ref_out", params, "--losses_out", losses,
        "--steps", "3", "--elastic",
    )
    assert run.returncode == 0, f"{run.stdout}\n{run.stderr}"
    assert "resumed=True" in run.stdout
    ref_losses = np.load(dp8_run["losses"])
    np.testing.assert_allclose(
        np.load(losses), ref_losses[2:], rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        np.load(params), np.load(dp8_run["params"]), rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        np.load(params + ".opt.npy"),
        np.load(dp8_run["params"] + ".opt.npy"),
        rtol=1e-4, atol=1e-6,
    )


def test_resume_at_different_device_count_refused_without_elastic(dp8_run, tmp_path):
    run = _run_script(
        _subprocess_env(device_count=4),
        "--phase", "verify", "--project_dir", dp8_run["project"],
        "--ref_out", str(tmp_path / "got.npy"),
    )
    assert run.returncode != 0
    assert "CheckpointTopologyError" in run.stderr
    assert "num_devices 8 (saved) != 4 (live)" in run.stderr


# ------------------------------------------- host loss + world-size change
class _NumpySGD:
    """Deterministic pure-numpy regression trainer registered for
    checkpointing: its state rides save_state/load_state as a custom
    object, so the cluster test exercises the full commit → replicate →
    consensus → replica-restore → topology-gate path with real processes
    while keeping every array process-local (this jaxlib's CPU backend
    cannot run cross-process XLA programs; the coordination-service
    barrier/allgather fallbacks are exactly what multi-process
    checkpointing rides here)."""

    LR = 0.05

    def __init__(self):
        self.a = 0.0
        self.b = 0.0
        self.step = 0

    def state_dict(self):
        return {"a": self.a, "b": self.b, "step": self.step}

    def load_state_dict(self, sd):
        self.a = float(sd["a"])
        self.b = float(sd["b"])
        self.step = int(sd["step"])

    def train_step(self):
        x = np.arange(16.0) / 16.0
        y = 2.0 * x + 3.0
        err = self.a * x + self.b - y
        self.a -= self.LR * 2.0 * float(np.mean(err * x))
        self.b -= self.LR * 2.0 * float(np.mean(err))
        self.step += 1
        return float(np.mean(err**2))


def _cluster_train_crash_body(project, replica, crash_rank):
    import os as _os
    import signal as _signal

    from accelerate_tpu import Accelerator as _Accelerator
    from accelerate_tpu.utils.dataclasses import ReplicationConfig as _RC

    acc = _Accelerator(
        project_dir=project,
        replication_config=_RC(target=replica, async_replicate=False),
    )
    acc.project_configuration.automatic_checkpoint_naming = True
    assert acc.num_processes == 4
    trainer = _NumpySGD()
    acc.register_for_checkpointing(trainer)
    for _ in range(2):
        trainer.train_step()
    acc.save_state()  # checkpoint_0, fully mirrored before returning
    if acc.process_index == crash_rank:
        # host loss: die hard AFTER the commit+mirror; the survivors return
        # without entering another collective, the parent observes the
        # unreported rank
        _os.kill(_os.getpid(), _signal.SIGKILL)


def _cluster_run_body(project, replica, resume, steps, losses_out, params_out):
    import numpy as _np

    from accelerate_tpu import Accelerator as _Accelerator
    from accelerate_tpu.utils.dataclasses import ReplicationConfig as _RC

    acc = _Accelerator(
        project_dir=project,
        replication_config=_RC(target=replica, async_replicate=False),
    )
    acc.project_configuration.automatic_checkpoint_naming = True
    assert acc.num_processes == 2
    trainer = _NumpySGD()
    acc.register_for_checkpointing(trainer)
    if resume:
        # consensus over empty local trees → replica restore → elastic
        # topology downgrade (manifest says num_processes=4, live is 2)
        assert acc.resume_from_latest(elastic=True) is True
        assert trainer.step == 2, trainer.step
    losses = [trainer.train_step() for _ in range(steps)]
    if acc.is_main_process:
        _np.save(losses_out, _np.asarray(losses, _np.float64))
        _np.save(params_out, _np.asarray([trainer.a, trainer.b], _np.float64))
    acc.end_training()


@pytest.mark.slow
def test_host_loss_with_world_size_change_resumes_via_replica(tmp_path):
    """The acceptance criterion end to end: train at n=4 with replication,
    SIGKILL one rank after the commit, wipe the whole local checkpoint tree,
    gang-restart at n=2 — the job resumes from the cluster-consensus
    checkpoint via replica restore (elastic reshard 4→2), and the
    post-resume loss trajectory matches an uninterrupted same-seed n=2 run."""
    from accelerate_tpu.launchers import _free_port, _spawn_cluster

    project = str(tmp_path / "proj")
    replica = str(tmp_path / "replica")

    # phase A: n=4 trains, checkpoints (sync-replicated), rank 1 dies hard
    with pytest.raises(RuntimeError, match="died without reporting"):
        _spawn_cluster(
            _cluster_train_crash_body, (project, replica, 1),
            num_processes=4, local_devices=1, port=_free_port(), timeout=120,
        )
    assert os.path.isfile(
        os.path.join(replica, "r0", "checkpoint_0", "COMMITTED")
    )
    # the surviving infrastructure loses every local checkpoint too
    shutil.rmtree(os.path.join(project, "checkpoints"))

    # phase B: gang-restart at n=2, consensus finds nothing local, replica
    # restore + elastic reshard, then 3 more steps
    resumed_losses = str(tmp_path / "resumed_losses.npy")
    resumed_params = str(tmp_path / "resumed_params.npy")
    _spawn_cluster(
        _cluster_run_body,
        (project, replica, True, 3, resumed_losses, resumed_params),
        num_processes=2, local_devices=1, port=_free_port(), timeout=300,
    )

    # reference: uninterrupted same-seed n=2 run, 5 steps
    ref_losses = str(tmp_path / "ref_losses.npy")
    ref_params = str(tmp_path / "ref_params.npy")
    _spawn_cluster(
        _cluster_run_body,
        (str(tmp_path / "ref_proj"), str(tmp_path / "ref_replica"), False, 5,
         ref_losses, ref_params),
        num_processes=2, local_devices=1, port=_free_port(), timeout=300,
    )

    # pure-float64 training through a pickle save/restore roundtrip is
    # bit-exact: the resumed trajectory must MATCH, not approximate
    np.testing.assert_array_equal(np.load(resumed_losses), np.load(ref_losses)[2:])
    np.testing.assert_array_equal(np.load(resumed_params), np.load(ref_params))


def _cluster_first_launch_body(project, replica):
    from accelerate_tpu import Accelerator as _Accelerator
    from accelerate_tpu.utils.dataclasses import ReplicationConfig as _RC

    acc = _Accelerator(
        project_dir=project,
        replication_config=_RC(target=replica, async_replicate=False),
    )
    acc.project_configuration.automatic_checkpoint_naming = True
    assert acc.num_processes == 2
    assert acc.resume_from_latest() is False
    acc.end_training()


@pytest.mark.slow
def test_first_launch_with_replication_multiprocess_returns_false(tmp_path):
    """Regression: first launch with replication configured but no replicas
    yet must return False on EVERY rank. Main's restore_from_replica used to
    raise CheckpointNotFoundError past the replica-restore rendezvous,
    wedging the other ranks at it (up to the coordination-service cap)
    while main started training — the consensus failure now travels to
    every rank as data and the whole gang agrees it is a first launch."""
    from accelerate_tpu.launchers import _free_port, _spawn_cluster

    _spawn_cluster(
        _cluster_first_launch_body,
        (str(tmp_path / "proj"), str(tmp_path / "replica")),
        num_processes=2, local_devices=1, port=_free_port(), timeout=120,
    )


def _cluster_corrupt_heal_body(project, replica):
    import os as _os

    from accelerate_tpu import Accelerator as _Accelerator
    from accelerate_tpu.utils.dataclasses import ReplicationConfig as _RC

    acc = _Accelerator(
        project_dir=project,
        replication_config=_RC(target=replica, async_replicate=False),
    )
    acc.project_configuration.automatic_checkpoint_naming = True
    assert acc.num_processes == 2
    trainer = _NumpySGD()
    acc.register_for_checkpointing(trainer)
    trainer.train_step()
    ckpt = acc.save_state()
    trainer.a, trainer.b, trainer.step = 99.0, 99.0, 99
    if acc.is_main_process:
        # same-size bit-flip: only the checksum proof can catch it
        victim = _os.path.join(ckpt, "custom_checkpoint_0.pkl")
        size = _os.path.getsize(victim)
        with open(victim, "wb") as f:
            f.write(b"X" * size)
    acc.wait_for_everyone()
    acc.load_state(ckpt, verify="checksum")  # collective park + replica heal
    assert trainer.step == 1, trainer.step
    acc.end_training()


@pytest.mark.slow
def test_corrupt_checkpoint_healed_collectively_in_cluster(tmp_path):
    """A corrupt tree discovered at load time in a multi-process job routes
    the WHOLE gang through the same verify-verdict gather, park barrier, and
    collective replica restore — no rank renames until every rank has
    finished verifying, and no rank skips the restore collectives."""
    from accelerate_tpu.launchers import _free_port, _spawn_cluster

    _spawn_cluster(
        _cluster_corrupt_heal_body,
        (str(tmp_path / "proj"), str(tmp_path / "replica")),
        num_processes=2, local_devices=1, port=_free_port(), timeout=180,
    )
