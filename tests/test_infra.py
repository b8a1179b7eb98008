"""Substrate tests: data pipeline, optimizer, compression, checkpointing,
distributed utilities, serving engine."""
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import SyntheticTokens, MemmapTokens, make_source
from repro.optim import adamw
from repro.optim.compression import (PowerSGDConfig, compress_decompress, init
                                     as psgd_init)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# -------------------------------------------------------------------- data

def test_synthetic_deterministic_per_step():
    s = SyntheticTokens(vocab_size=97, seq_len=16, batch=3, seed=5)
    a, b = s.batch_at(7)["tokens"], s.batch_at(7)["tokens"]
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, s.batch_at(8)["tokens"])
    assert a.shape == (3, 17) and a.min() >= 0 and a.max() < 97


def test_synthetic_has_learnable_structure():
    """Markov structure: next-token is predictable more often than chance."""
    s = SyntheticTokens(vocab_size=101, seq_len=256, batch=8, seed=1)
    t = s.batch_at(0)["tokens"]
    pred = (t[:, :-1] * 97 + 13) % 101
    hit = (pred == t[:, 1:]).mean()
    assert hit > 0.3


def test_memmap_source(tmp_path):
    path = str(tmp_path / "toks.bin")
    np.arange(10_000, dtype=np.uint16).tofile(path)
    src = MemmapTokens(path=path, seq_len=32, batch=4)
    b = src.batch_at(0)["tokens"]
    assert b.shape == (4, 33)
    np.testing.assert_array_equal(np.diff(b, axis=1), 1)  # consecutive ids


def test_host_sharded_sources_disjoint_streams():
    a = make_source(101, 16, 2, seed=0, host_index=0, host_count=2)
    b = make_source(101, 16, 2, seed=0, host_index=1, host_count=2)
    assert not np.array_equal(a.batch_at(0)["tokens"], b.batch_at(0)["tokens"])


# ------------------------------------------------------------------- optim

def test_adamw_converges_on_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200,
                            weight_decay=0.0, schedule="constant")
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = adamw.init(params)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        params, state, _ = adamw.apply_updates(params, g, state, cfg)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


def test_schedule_shapes():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_ratio=0.1)
    lrs = [float(adamw.schedule_lr(cfg, jnp.asarray(s))) for s in range(101)]
    assert lrs[0] == 0.0 and lrs[10] == pytest.approx(1.0)
    assert lrs[100] == pytest.approx(0.1, rel=1e-3)
    assert all(a >= b - 1e-9 for a, b in zip(lrs[10:], lrs[11:]))  # decay


# ------------------------------------------------------------ compression

def test_powersgd_compresses_and_converges_with_error_feedback():
    rng = np.random.default_rng(0)
    # low-rank-ish gradient
    g_true = rng.standard_normal((64, 48, 2)).astype(np.float32)
    params = {"w": jnp.zeros((64, 96))}
    cfg = PowerSGDConfig(rank=4, min_compress_size=1)
    state = psgd_init(params, cfg)
    grads = {"w": jnp.asarray((g_true[..., 0] @ g_true[..., 1].T.reshape(48, -1)[:, :96]
                               if False else rng.standard_normal((64, 96)))
                              .astype(np.float32))}
    approx, state, metrics = compress_decompress(grads, state, cfg)
    assert metrics["powersgd_ratio"] < 0.2
    # error feedback: accumulated residual + next approx recovers more energy
    resid0 = float(jnp.linalg.norm(grads["w"] - approx["w"]))
    approx2, state, _ = compress_decompress(grads, state, cfg)
    # after EF warmup the *cumulative* transmitted signal approaches g
    total = approx["w"] + approx2["w"]
    assert float(jnp.linalg.norm(grads["w"] * 2 - total)) <= resid0 * 2 + 1e-3


def test_powersgd_exact_for_rank_leq_r():
    rng = np.random.default_rng(1)
    lr_grad = (rng.standard_normal((32, 3)) @ rng.standard_normal((3, 40))).astype(np.float32)
    params = {"w": jnp.zeros((32, 40))}
    cfg = PowerSGDConfig(rank=8, min_compress_size=1, ef=False)
    state = psgd_init(params, cfg)
    approx, _, _ = compress_decompress({"w": jnp.asarray(lr_grad)}, state, cfg)
    np.testing.assert_allclose(np.asarray(approx["w"]), lr_grad, atol=1e-3)


# -------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip_and_gc(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "opt": {"mu": jnp.ones(4)}}
    for s in (1, 2, 3):
        m.save(s, jax.tree.map(lambda x: x * s, tree))
    assert m.all_steps() == [2, 3]
    restored, step = m.restore(tree)
    assert step == 3
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.arange(6.0).reshape(2, 3) * 3)


def test_checkpoint_ignores_torn_writes(tmp_path):
    m = CheckpointManager(str(tmp_path), async_save=False)
    tree = {"a": jnp.ones(3)}
    m.save(5, tree)
    # simulate a crash mid-write: step dir without COMMIT
    torn = tmp_path / "step_000000009"
    torn.mkdir()
    (torn / "shard_00000.npz").write_bytes(b"garbage")
    assert m.latest_step() == 5
    _, step = m.restore(tree)
    assert step == 5


def test_checkpoint_elastic_placer(tmp_path):
    """restore() re-places arrays through a custom placer (resharding hook)."""
    m = CheckpointManager(str(tmp_path), async_save=False)
    tree = {"w": jnp.arange(8.0)}
    m.save(1, tree)
    seen = []
    restored, _ = m.restore(tree, placer=lambda k, a: seen.append(k) or jnp.asarray(a) * 0 + 7)
    assert seen and float(restored["w"][0]) == 7.0


def test_async_save_overlaps_and_completes(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=5, async_save=True)
    tree = {"a": jnp.ones((256, 256))}
    m.save(1, tree)
    m.save(2, tree)   # waits for 1, launches 2
    m.wait()
    assert m.all_steps() == [1, 2]


# ------------------------------------------------------------- distributed

def test_logical_to_spec_conflict_resolution():
    from repro.distributed.meshctx import logical_to_spec
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    # (out_axis='heads', rank) -> model taken by heads, rank replicated
    spec = logical_to_spec(mesh, ("heads", "rank"))
    assert spec == jax.sharding.PartitionSpec("model", None)
    spec = logical_to_spec(mesh, ("embed", "rank"))
    assert spec == jax.sharding.PartitionSpec(None, "model")


def test_elastic_remesh_shrinks_data_axis():
    from repro.distributed.sharding import elastic_remesh
    devs = jax.devices()
    mesh = elastic_remesh((4, 1), ("data", "model"), devices=devs)
    assert mesh.shape["data"] == len(devs)  # shrank 4 -> available


def test_straggler_monitor_flags_outliers():
    from repro.distributed.sharding import StragglerMonitor
    mon = StragglerMonitor(window=20, threshold=2.0)
    flagged = [mon.record(0.1) for _ in range(10)]
    assert not any(flagged)
    assert mon.record(0.5) is True


def test_preemption_guard_sets_flag():
    import signal
    from repro.distributed.sharding import PreemptionGuard
    g = PreemptionGuard(signals=(signal.SIGUSR1,))
    os.kill(os.getpid(), signal.SIGUSR1)
    assert g.requested
    g.restore()


# ----------------------------------------------------------------- serving

def test_serving_engine_budget_mapping_and_order():
    from repro.launch.serve import main as serve_main
    results = serve_main(["--arch", "gpt2-small", "--smoke", "--requests", "3",
                          "--max-new", "2", "--prompt-len", "4",
                          "--budgets", "0.4,1.0"])
    assert len(results) == 3
    assert results[1].deployed_params >= results[0].deployed_params


# ------------------------------------------------------- restart integration

def test_train_restart_resumes(tmp_path):
    from repro.launch.train import main as train_main
    ck = str(tmp_path / "ck")
    args = ["--arch", "gpt2-small", "--smoke", "--steps", "8",
            "--ckpt-dir", ck, "--ckpt-every", "4", "--seq-len", "32",
            "--batch", "2"]
    train_main(args)
    # second invocation must resume from step 8 and do nothing more
    params, losses = train_main(args)
    assert losses == []


def test_train_mesh_places_fresh_and_restored_state(tmp_path):
    """``--mesh-shape 4,1`` on four virtual devices (a subprocess: the
    suite keeps one): a fresh start and a restart from its checkpoint both
    put the training state on all four devices, and the batch is split
    across them."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.launch.train import main
        args = ["--arch", "gpt2-small", "--smoke", "--mode", "flexrank_kd",
                "--seq-len", "16", "--batch", "4", "--ckpt-every", "2",
                "--ckpt-dir", {str(tmp_path / "ck")!r}, "--mesh-shape", "4,1"]
        fresh, _ = main(args + ["--steps", "2"])
        restored, losses = main(args + ["--steps", "3"])
        assert len(losses) == 1, losses
        for params in (fresh, restored):
            assert all(len(a.sharding.device_set) == 4
                       for a in jax.tree.leaves(params))
        print("PLACED")
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "PLACED" in res.stdout


# -------------------------------------------------------------------- muon

def test_newton_schulz_orthogonalizes():
    from repro.optim.muon import newton_schulz
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((24, 16)).astype(np.float32))
    o = newton_schulz(g, steps=5)
    gram = np.asarray(o.T @ o)
    # singular values pushed toward 1 (approximate msign)
    sv = np.linalg.svd(np.asarray(o), compute_uv=False)
    assert sv.max() < 1.6 and sv.min() > 0.3, sv


def test_muon_converges_and_beats_nothing_broken():
    from repro.optim import muon
    rng = np.random.default_rng(1)
    target = jnp.asarray(rng.standard_normal((8, 6)).astype(np.float32))
    params = {"w": jnp.zeros((8, 6)), "b": jnp.zeros(6)}
    cfg = muon.MuonConfig(lr=0.05,
                          adamw=__import__("repro.optim.adamw", fromlist=["AdamWConfig"]).AdamWConfig(
                              lr=0.05, warmup_steps=0, schedule="constant",
                              weight_decay=0.0))
    state = muon.init(params, cfg)
    def loss(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum((p["b"] - 1.0) ** 2)
    l0 = float(loss(params))
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state, _ = muon.apply_updates(params, g, state, cfg)
    assert float(loss(params)) < 0.05 * l0


def test_muon_stacked_layers_vmap():
    from repro.optim import muon
    params = {"w": jnp.zeros((3, 8, 6))}  # stacked (L, m, n)
    cfg = muon.MuonConfig(lr=0.1)
    state = muon.init(params, cfg)
    g = {"w": jnp.ones((3, 8, 6))}
    p2, state, _ = muon.apply_updates(params, g, state, cfg)
    assert p2["w"].shape == (3, 8, 6)
    assert float(jnp.abs(p2["w"]).max()) > 0
