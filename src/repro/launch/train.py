"""Training launcher (CLI).

    PYTHONPATH=src python -m repro.launch.train \
        --arch tinyllama_1_1b --smoke --steps 200 --batch 8 --seq 128 \
        --ckpt-dir /tmp/ckpt --resume

Production posture: mesh from --mesh (host devices), sharded state via the
DESIGN.md §5 rules, atomic+async checkpoints every --ckpt-every steps,
preemption-safe (SIGTERM -> final checkpoint), --resume restores params,
optimizer, step and data-iterator state. --sparsity enables mask-preserving
sparse training (the paper's retraining-based pruning loop).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import pruning
from repro.distributed import fault_tolerance as ft
from repro.distributed import sharding
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.training import data as data_mod
from repro.training import optimizer as opt_mod
from repro.training import train_loop


def sharded_step(step_fn, state: train_loop.TrainState, mesh):
    """The --mesh train step, jitted with the state sharded over ``mesh``
    (params by the DESIGN.md §5 rules, AdamW moments like their params,
    step counters replicated) and donated; call it inside
    ``jax.set_mesh(mesh)``."""
    p_sh = sharding.params_shardings(state.params, mesh)
    o_sh = opt_mod.AdamWState(
        step=sharding.replicated(mesh),
        mu=jax.tree.map(lambda _, s: s, state.opt_state.mu, p_sh),
        nu=jax.tree.map(lambda _, s: s, state.opt_state.nu, p_sh))
    s_sh = train_loop.TrainState(p_sh, o_sh, sharding.replicated(mesh))
    return jax.jit(step_fn, in_shardings=(s_sh, None), donate_argnums=(0,))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--sparsity", type=float, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help='"DxM" over local devices, e.g. "4x2"')
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    opt = opt_mod.AdamW(lr=opt_mod.cosine_schedule(
        args.lr, args.warmup, args.steps))

    state = train_loop.init_train_state(jax.random.PRNGKey(args.seed), cfg,
                                        opt)
    masks = None
    if args.sparsity:
        masks = jax.tree_util.tree_map_with_path(
            lambda p, x: (pruning.unstructured_mask(jnp.abs(x),
                                                    args.sparsity)
                          if x.ndim == 3 and "'w'" in
                          jax.tree_util.keystr(p) else None),
            state.params)
        state = train_loop.TrainState(
            opt_mod.apply_masks(state.params, masks),
            state.opt_state, state.step)

    stream = data_mod.SyntheticLM(cfg.vocab, args.seq, args.batch,
                                  seed=args.seed,
                                  n_codebooks=cfg.n_codebooks)
    mgr = (ft.CheckpointManager(args.ckpt_dir, keep=3, async_save=True)
           if args.ckpt_dir else None)
    preempt = ft.PreemptionHandler()

    start = 0
    if args.resume and mgr and mgr.latest_step() is not None:
        (state, data_state), meta = mgr.restore((state, stream.state_dict()))
        stream.load_state_dict(jax.tree.map(int, data_state))
        start = meta["step"]
        print(f"resumed from step {start}")

    step_fn = train_loop.make_train_step(cfg, opt, masks=masks,
                                         microbatches=args.microbatches)
    if args.mesh:
        d, m = map(int, args.mesh.split("x"))
        mesh = mesh_mod.make_mesh((d, m), ("data", "model"))
        ctx = jax.set_mesh(mesh)
        step_fn = sharded_step(step_fn, state, mesh)
    else:
        import contextlib
        ctx = contextlib.nullcontext()
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    with ctx:
        t0 = time.time()
        for s in range(start, args.steps):
            batch = jax.tree.map(jnp.asarray, stream.next_batch())
            state, metrics = step_fn(state, batch)
            if (s + 1) % args.log_every == 0:
                dt = (time.time() - t0) / args.log_every
                tok_s = args.batch * args.seq / dt
                print(f"step {s + 1:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{tok_s:,.0f} tok/s", flush=True)
                t0 = time.time()
            if mgr and ((s + 1) % args.ckpt_every == 0 or preempt.should_stop):
                mgr.save(s + 1, (state, stream.state_dict()))
            if preempt.should_stop:
                print("preemption: final checkpoint written; exiting")
                break
    if mgr:
        mgr.save(args.steps, (state, stream.state_dict()), block=True)
        mgr.wait()
    print("done")


if __name__ == "__main__":
    main()
