"""Mixed-precision AdamW with cosine schedule and global-norm clipping.

Pure-JAX pytree optimizer (no optax on this box).  Designed for the memory
budget of the large LM configs: model params may be bf16;
the optimizer keeps an fp32 master copy and (configurably) bf16 moments, so
nemotron-4-340b's state is 10 bytes/param — the difference between fitting
and not fitting 256×16 GB.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


def cosine_schedule(step: jax.Array, *, peak_lr: float, warmup: int, total: int, floor: float = 0.1):
    warm = peak_lr * (step + 1) / max(warmup, 1)
    frac = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
    cos = peak_lr * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warmup, warm, cos).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    moment_dtype: Any = jnp.bfloat16
    #: keep an fp32 master copy when params are lower precision
    master_weights: bool = True


@dataclasses.dataclass(frozen=True)
class AdamW:
    cfg: AdamWConfig = AdamWConfig()

    def init(self, params) -> dict:
        zeros = lambda p: jnp.zeros(p.shape, self.cfg.moment_dtype)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
        }
        if self.cfg.master_weights:
            state["master"] = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return state

    def update(self, grads, state, params):
        c = self.cfg
        step = state["step"]
        lr = cosine_schedule(step, peak_lr=c.peak_lr, warmup=c.warmup, total=c.total_steps)

        gnorm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
        )
        scale = jnp.minimum(1.0, c.clip_norm / jnp.maximum(gnorm, 1e-12))

        t = (step + 1).astype(jnp.float32)
        bc1 = 1.0 - c.b1**t
        bc2 = 1.0 - c.b2**t
        masters = state.get("master", params)

        def upd(g, mu, nu, m):
            g = g.astype(jnp.float32) * scale
            mu32 = c.b1 * mu.astype(jnp.float32) + (1 - c.b1) * g
            nu32 = c.b2 * nu.astype(jnp.float32) + (1 - c.b2) * g * g
            mhat = mu32 / bc1
            vhat = nu32 / bc2
            m32 = m.astype(jnp.float32)
            m32 = m32 - lr * (mhat / (jnp.sqrt(vhat) + c.eps) + c.weight_decay * m32)
            return m32, mu32.astype(c.moment_dtype), nu32.astype(c.moment_dtype)

        flat_g, treedef = jax.tree.flatten(grads)
        flat_mu = jax.tree.leaves(state["mu"])
        flat_nu = jax.tree.leaves(state["nu"])
        flat_m = jax.tree.leaves(masters)
        out = [upd(*args) for args in zip(flat_g, flat_mu, flat_nu, flat_m)]
        new_master = treedef.unflatten([o[0] for o in out])
        new_mu = treedef.unflatten([o[1] for o in out])
        new_nu = treedef.unflatten([o[2] for o in out])

        new_params = jax.tree.map(lambda m, p: m.astype(p.dtype), new_master, params)
        new_state = {"step": step + 1, "mu": new_mu, "nu": new_nu}
        if c.master_weights:
            new_state["master"] = new_master
        return new_params, new_state, {"lr": lr, "grad_norm": gnorm}
