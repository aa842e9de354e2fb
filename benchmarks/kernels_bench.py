"""Kernel micro-benchmarks (the paper's Im2Col+GEMM operators, §6).

Every kernel here runs in Pallas interpret mode on the CPU
(``interpret=True``), so the times are interpret-mode times on the CPU, not
kernel times: nobody deploys the interpreter.  What IS meaningful here is
(a) correctness at these sizes vs the XLA reference and (b) the
arithmetic-intensity table used to pick BlockSpecs.  Kernel times come only
from a run on the TPU.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import csv_row, save


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, out


def run(verbose: bool = True) -> list[str]:
    key = jax.random.PRNGKey(0)
    rows = []

    # GEMM (paper's conv operator #2): MXU tile 128x128xK
    m, k, n = 512, 512, 512
    a = jax.random.normal(key, (m, k), jnp.float32)
    b = jax.random.normal(key, (k, n), jnp.float32)
    t_ref, y_ref = _time(lambda x, y: ref.gemm_ref(x, y), a, b)
    t_k, y_k = _time(lambda x, y: ops.gemm(x, y, interpret=True), a, b)
    err = float(jnp.max(jnp.abs(y_ref - y_k)))
    ai = 2 * m * k * n / ((m * k + k * n + m * n) * 4)
    rows.append(csv_row("gemm_512_interp", t_k, f"xla_ref_us={t_ref:.0f};max_err={err:.1e};arith_intensity={ai:.0f}"))

    # Im2Col conv (paper's operator #1): AlexNet conv3 shape
    x = jax.random.normal(key, (1, 13, 13, 256), jnp.float32)
    w = jax.random.normal(key, (3, 3, 256, 384), jnp.float32)
    t_ref, y_ref = _time(lambda x, w: ref.conv2d_ref(x, w), x, w)
    t_k, y_k = _time(lambda x, w: ops.conv2d_im2col(x, w, interpret=True), x, w)
    err = float(jnp.max(jnp.abs(y_ref - y_k)))
    rows.append(csv_row("im2col_conv_alexnet3_interp", t_k, f"xla_ref_us={t_ref:.0f};max_err={err:.1e}"))

    # Flash attention
    q = jax.random.normal(key, (1, 4, 256, 64), jnp.float32)
    kk = jax.random.normal(key, (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(key, (1, 2, 256, 64), jnp.float32)
    t_ref, y_ref = _time(lambda q, k, v: ref.attention_ref(q, k, v), q, kk, v)
    t_k, y_k = _time(lambda q, k, v: ops.flash_attention(q, k, v, bq=128, bk=128, interpret=True), q, kk, v)
    err = float(jnp.max(jnp.abs(y_ref - y_k)))
    rows.append(csv_row("flash_attn_s256_interp", t_k, f"xla_ref_us={t_ref:.0f};max_err={err:.1e}"))

    # SSD scan
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (1, 256, 4, 32), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 256, 4)))
    A = -jnp.exp(jax.random.normal(ks[2], (4,)) * 0.5)
    B = jax.random.normal(ks[3], (1, 256, 16))
    C = jax.random.normal(ks[4], (1, 256, 16))
    t_ref, y_ref = _time(lambda *a: ref.ssd_ref(*a), x, dt, A, B, C)
    t_k, y_k = _time(lambda *a: ops.ssd_scan(*a, chunk=64, interpret=True), x, dt, A, B, C)
    err = float(jnp.max(jnp.abs(y_ref - y_k)))
    rows.append(csv_row("ssd_scan_s256_interp", t_k, f"xla_ref_us={t_ref:.0f};max_err={err:.1e}"))

    if verbose:
        for r in rows:
            print("  kern (interpret mode on CPU)", r)
    save("kernels_bench", {"rows": rows})
    return rows


if __name__ == "__main__":
    run()
