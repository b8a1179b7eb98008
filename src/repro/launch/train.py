"""Training launcher: dense pretraining or FlexRank consolidation, with the
full fault-tolerance story — checkpoint/restart, preemption handling,
straggler monitoring, elastic re-mesh on device loss, optional PowerSGD
gradient compression across the data axes.

Local-scale example (CPU, smoke config):
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt --mode flexrank_kd

Cluster-scale: same entrypoint; the mesh shape comes from --mesh-shape and
shrinks elastically (distributed.elastic_remesh) if devices are lost between
restarts. Data is step-indexed, so a restart at step k consumes exactly the
batches it would have seen — no data-state checkpointing needed.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import flexrank as FR
from repro.data import make_source, calibration_batches
from repro.distributed import (PreemptionGuard, StragglerMonitor, elastic_remesh,
                               mesh_context, param_shardings)
from repro.distributed.sharding import batch_sharding, replicated
from repro.launch import specs as SP
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import single_device_mesh
from repro.models import common as cm
from repro.models import transformer as tfm
from repro.optim import adamw


def build_flexrank_state(cfg, dense_params, source, *, calib_batches=8):
    """Paper Algorithm 1 stages 1-2: calibrate, decompose, DP-select."""
    cal = calibration_batches(source, calib_batches)
    moments = FR.collect_moments(dense_params, cfg, cal)
    fact_params, curves = FR.decompose(dense_params, cfg, moments)
    table, infos = FR.build_table(cfg, curves)
    return fact_params, table, infos


def state_shardings(mesh, axes, params, opt_state):
    """Where the training state lives on ``mesh``: parameters by their
    logical ``axes`` (``param_shardings``), every optimizer subtree laid
    out like the parameters (the moments) likewise, and everything else
    (step counters, empty placeholders) replicated."""
    pshard = param_shardings(mesh, axes, params)
    rep = replicated(mesh)
    pdef = jax.tree.structure(params)

    def like_params(t):
        return jax.tree.structure(t) == pdef

    def moments(t):
        return jax.tree.map(lambda a, p, sh: sh if a.shape == p.shape else rep,
                            t, params, pshard)

    oshard = jax.tree.map(lambda t: moments(t) if like_params(t) else rep,
                          opt_state, is_leaf=like_params)
    return pshard, oshard


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="dense",
                    choices=["dense", "flexrank", "flexrank_kd"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh-shape", default=None,
                    help="e.g. 16,16 — default single device")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "muon"],
                    help="muon: Newton-Schulz orthogonalized momentum for "
                         "matrix params (paper §7's suggested direction)")
    ap.add_argument("--grad-compress", action="store_true",
                    help="PowerSGD low-rank gradient compression (logged only "
                         "on 1 device; compresses DP all-reduce on a mesh)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
        mesh = elastic_remesh(shape, ("data", "model")[: len(shape)])
    else:
        mesh = single_device_mesh()

    source = make_source(cfg.vocab_size, args.seq_len, args.batch, seed=args.seed)
    guard = PreemptionGuard()
    monitor = StragglerMonitor()
    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None

    with mesh_context(mesh):
        key = jax.random.PRNGKey(args.seed)
        spec = tfm.model_spec(cfg)
        dense_params = cm.instantiate(spec, key)

        # ------- FlexRank prep (Algorithm 1, stages 1-2) -------
        infos = table = None
        if args.mode.startswith("flexrank"):
            params, table, infos = build_flexrank_state(cfg, dense_params, source)
            table_dev = FR.table_device(table)
            print(f"[flexrank] {len(infos)} groups, {table.table.shape[0]} nested budgets")
        else:
            params = dense_params

        opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                                    total_steps=args.steps)
        if args.optimizer == "muon":
            from repro.optim import muon as muon_mod
            muon_cfg = muon_mod.MuonConfig(lr=args.lr * 10, adamw=opt_cfg)
            opt_state = muon_mod.init(params, muon_cfg)
        else:
            opt_state = adamw.init(params)

        # ------- restart path -------
        start_step = 0
        if mgr and mgr.latest_step() is not None:
            # host arrays: the placement below shards them like a fresh start
            (params, opt_state), start_step = mgr.restore(
                (params, opt_state), placer=lambda k, a: a)
            print(f"[restart] resumed from step {start_step}")

        # ------- placement on the mesh -------
        axes = cm.axes_tree(FR.factorized_spec(cfg) if infos else spec)
        shardings = state_shardings(mesh, axes, params, opt_state)
        params, opt_state = jax.device_put((params, opt_state), shardings)
        data_sharding = batch_sharding(mesh)

        # ------- step fn -------
        if args.optimizer == "muon":
            from repro.optim import muon as muon_mod
            apply_fn = lambda p, g, st: muon_mod.apply_updates(p, g, st, muon_cfg)
        else:
            apply_fn = lambda p, g, st: adamw.apply_updates(p, g, st, opt_cfg)

        if args.mode == "flexrank_kd":
            teacher = jax.device_put(dense_params, param_shardings(
                mesh, cm.axes_tree(spec), dense_params))
            loss_fn = FR.make_consolidation_loss(cfg, infos, FR.table_device(table),
                                                 teacher)

            @jax.jit
            def step_fn(params, opt_state, batch, rng):
                (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, batch, rng)
                params, opt_state, om = apply_fn(params, grads, opt_state)
                return params, opt_state, {"loss": loss, **om}
        elif args.optimizer == "muon":
            def _step(params, opt_state, batch, rng):
                from repro.core.distill import cross_entropy
                from repro.models import transformer as _T
                def loss_fn2(p):
                    toks = batch["tokens"][:, :-1]
                    labels = batch["tokens"][:, 1:]
                    logits, aux = _T.forward(p, cfg, toks)
                    return cross_entropy(logits, labels) + aux
                loss, grads = jax.value_and_grad(loss_fn2)(params)
                params, opt_state, om = apply_fn(params, grads, opt_state)
                return params, opt_state, {"loss": loss, **om}
            step_fn = jax.jit(_step)
        else:
            train_step = SP.make_train_step(cfg, opt_cfg, mode=args.mode)
            step_fn = jax.jit(train_step)

        # ------- loop -------
        losses = []
        for step in range(start_step, args.steps):
            batch = {"tokens": jax.device_put(source.batch_at(step)["tokens"],
                                              data_sharding)}
            rng = jax.random.fold_in(jax.random.PRNGKey(args.seed + 1), step)
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, batch, rng)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            if monitor.record(dt):
                print(f"[straggler] step {step} took {dt:.2f}s (median {monitor.median:.2f}s)")
            losses.append(float(metrics["loss"]))
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {losses[-1]:.4f} "
                      f"lr {float(metrics['lr']):.2e} {dt*1000:.0f}ms")
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, (params, opt_state))
            if guard.requested:
                print(f"[preempt] checkpoint at step {step + 1} and exit")
                if mgr:
                    mgr.save(step + 1, (params, opt_state), blocking=True)
                return params, losses
        if mgr:
            mgr.save(args.steps, (params, opt_state), blocking=True)

        # ------- elastic eval across budgets -------
        if infos:
            batch = {"tokens": jnp.asarray(source.batch_at(10_000)["tokens"])}
            tdev = FR.table_device(table)
            print("[elastic eval] per-budget CE:")
            for k in range(table.table.shape[0]):
                ce = FR.eval_budget_loss(params, cfg, infos, tdev, batch, k)
                print(f"  budget {table.budgets[min(k, len(table.budgets)-1)]:.2f} "
                      f"(row {k}): {ce:.4f}")
        return params, losses


if __name__ == "__main__":
    main()
