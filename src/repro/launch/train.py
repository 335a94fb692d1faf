"""End-to-end training driver.

Wires every substrate together: config -> mesh -> sharded params/optimizer ->
step-indexed data -> jitted train step -> async checkpoints -> crash-only
supervision.  Runs the full-size configs on a real TPU mesh and the reduced
configs on this CPU container (``--reduced``), e.g.:

  python -m repro.launch.train --arch qwen2-0.5b --reduced --steps 50
  python -m repro.launch.train --arch musicgen-medium --reduced --steps 100 \
      --d-model 512 --layers 8          # ~100M-param class driver
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint.checkpointer import CheckpointManager
from repro.configs import get_arch, reduced_config
from repro.data.tokens import TokenStream
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import init_params
from repro.runtime.fault_tolerance import supervise
from repro.sharding import named, opt_specs, param_specs
from repro.train import AdamWConfig, adamw_init, make_train_step

__all__ = ["train_loop", "main"]


def train_loop(cfg, *, steps: int, batch: int, seq_len: int, mesh=None,
               ckpt_dir: str | None = None, save_every: int = 50,
               microbatches: int = 1, log_every: int = 10, seed: int = 0,
               resume: bool = True, fail_at: int | None = None) -> dict:
    """Returns final {"params", "opt", "step", "losses"}."""
    mesh = mesh or make_local_mesh(1, 1)
    ocfg = AdamWConfig(total_steps=steps)
    stream = TokenStream(cfg.vocab, batch, seq_len, seed=seed,
                         n_codebooks=cfg.n_codebooks)

    params = init_params(cfg, jax.random.PRNGKey(seed))
    opt = adamw_init(params)
    pspec = named(mesh, param_specs(params, mesh))
    ospec = named(mesh, opt_specs(params, mesh))
    params = jax.tree.map(jax.device_put, params, pspec)
    opt = jax.tree.map(jax.device_put, opt, ospec)

    step_fn = make_train_step(cfg, ocfg, num_microbatches=microbatches)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn,
                         in_shardings=(pspec, ospec, None),
                         donate_argnums=(0, 1))

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    state = {"params": params, "opt": opt, "step": 0}
    if mgr and resume:
        last = mgr.latest_step()
        if last is not None:
            state = mgr.restore(last, state, shardings=None)
            state["params"] = jax.tree.map(jax.device_put, state["params"], pspec)
            state["opt"] = jax.tree.map(jax.device_put, state["opt"], ospec)
            print(f"[train] resumed from step {last}")

    losses: list[float] = []
    t_last = time.time()
    injected = {"done": False}

    def run_step(step: int, state: dict) -> dict:
        if fail_at is not None and step == fail_at and not injected["done"]:
            injected["done"] = True   # fail once; replay must succeed
            raise RuntimeError("injected failure (test)")
        b = stream.batch_at(step)
        batch_dev = {k: jax.numpy.asarray(v) for k, v in b.items()}
        with jax.set_mesh(mesh):
            p, o, m = jitted(state["params"], state["opt"], batch_dev)
        loss = float(m["loss"])
        losses.append(loss)
        if step % log_every == 0:
            nonlocal t_last
            dt = time.time() - t_last
            t_last = time.time()
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} ({dt:.1f}s)")
        return {"params": p, "opt": o, "step": step}

    if mgr:
        state = supervise(run_step, state, steps=steps, ckpt_mgr=mgr,
                          save_every=save_every)
    else:
        for s in range(state["step"], steps):
            state = run_step(s, state)
            state["step"] = s + 1
    state["losses"] = losses
    return state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        over = {}
        if args.d_model:
            over.update(d_model=args.d_model,
                        n_heads=max(args.d_model // 64, 4),
                        n_kv_heads=max(args.d_model // 128, 2),
                        d_ff=args.d_model * 3 if cfg.d_ff else 0)
        if args.layers:
            over["n_layers"] = args.layers
        if args.vocab:
            over["vocab"] = args.vocab
        cfg = reduced_config(cfg, **over)
    mesh = make_production_mesh() if args.production_mesh else make_local_mesh()
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"batch={args.batch} seq={args.seq} steps={args.steps}")
    state = train_loop(cfg, steps=args.steps, batch=args.batch,
                       seq_len=args.seq, mesh=mesh, ckpt_dir=args.ckpt_dir,
                       save_every=args.save_every,
                       microbatches=args.microbatches, seed=args.seed)
    ls = state["losses"]
    if ls:
        k = max(len(ls) // 10, 1)
        print(f"[train] loss first-{k}-mean {np.mean(ls[:k]):.4f} -> "
              f"last-{k}-mean {np.mean(ls[-k:]):.4f}")


if __name__ == "__main__":
    main()
