"""Forward blocks: GQA attention, dense/MoE FFN, Mamba2 SSD.

All functions take the *per-layer* parameter slice (scan has already
stripped the leading [L] axis) and are shape-polymorphic in batch/sequence.

MoE dispatch (`moe_ffn_local`) is deliberately **local and sort-free**: it
runs per data-shard inside `shard_map`, so token routing never crosses
devices — expert weights are tensor-parallel on d_ff over the ``model``
axis and the only collective is the same psum a dense TP FFN needs.  This
keeps compiled MoE FLOPs proportional to *active* experts (top_k), which is
what the roofline table must reflect (DESIGN.md §4).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from .lm_common import LMConfig, cstr_act, cstr_custom, cstr_heads, rms_norm, rotary

# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _qkv(cfg: LMConfig, p: dict, x: jax.Array, positions: jax.Array):
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rotary(q, positions)
    k = rotary(k, positions)
    return cstr_heads(q, 2), cstr_heads(k, 2), cstr_heads(v, 2)


def _sdpa_chunk(cfg: LMConfig, qg, k, v, q_pos, *, causal: bool, window: int):
    """Exact attention for one q chunk.

    qg: [b, bq, kvh, g, d]; k/v: [b, skv, kvh, d] — or, under
    ``attn_repeat_kv`` (k/v pre-repeated per q-head and the group axis
    merged), qg: [b, bq, H, 1, d]; k/v: [b, skv, H, d].
    """
    d = qg.shape[-1]
    skv = k.shape[1]
    score_t = jnp.float32 if cfg.attn_fp32_scores else jnp.bfloat16
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(score_t) / math.sqrt(d)
    k_pos = jnp.arange(skv)
    mask = jnp.ones((qg.shape[1], skv), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask, scores, jnp.asarray(-jnp.inf, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(qg.dtype) \
        if cfg.attn_fp32_scores else jax.nn.softmax(scores, axis=-1).astype(qg.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def _sdpa(cfg: LMConfig, q, k, v, *, causal: bool, q_offset: jax.Array | int = 0, window: int = 0):
    """Blockwise softmax attention with GQA head grouping.

    q: [b, sq, h, d]; k/v: [b, skv, kvh, d].  ``q_offset`` is the absolute
    position of q[0].  ``window``: sliding-window size (0 = full).

    The q axis is swept in ``cfg.attn_q_block`` chunks under lax.scan with a
    rematerialized body, so live score buffers stay O(bq·skv) — this is the
    XLA stand-in for the Pallas flash kernel (kernels/flash_attention.py),
    with the same asymptotic memory behaviour.
    """
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    if cfg.attn_repeat_kv and group > 1:
        # shard attention over ALL q-heads: repeat K/V per head (each model
        # shard materializes only its heads' copies) and merge the group
        # axis — otherwise kvh < TP replicates score compute TP-fold.
        k = cstr_heads(jnp.repeat(k, group, axis=2), 2)
        v = cstr_heads(jnp.repeat(v, group, axis=2), 2)
        kvh, group = h, 1
    qg = q.reshape(b, sq, kvh, group, d)
    bq = cfg.attn_q_block
    if sq <= bq or sq % bq != 0:
        out = _sdpa_chunk(cfg, qg, k, v, jnp.arange(sq) + q_offset, causal=causal, window=window)
        return out.reshape(b, sq, h * d)

    nq = sq // bq
    # layout pin: chunk axis UNSHARDED, batch over DP, kv-heads over TP when
    # divisible — without this the residual stream's seq-sharding lands on
    # the chunk axis and SPMD falls back to "involuntary full remat"
    # (observed: per-chunk full replication on nemotron-4-340b).
    qc = qg.reshape(b, nq, bq, kvh, group, d).transpose(1, 0, 2, 3, 4, 5)
    qc = cstr_custom(qc, batch_axis=1, tp_axis_at=3)

    def body(i, q_chunk):
        q_pos = i * bq + jnp.arange(bq) + q_offset
        out = _sdpa_chunk(cfg, q_chunk, k, v, q_pos, causal=causal, window=window)
        return i + 1, cstr_custom(out, batch_axis=1, tp_axis_at=3)

    _, out = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), jnp.zeros((), jnp.int32), qc,
        unroll=cfg.scan_unroll,
    )
    out = cstr_custom(out, batch_axis=1, tp_axis_at=3)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h * d)


def attention(
    cfg: LMConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    return_kv: bool = False,
):
    """Full-sequence (train / prefill) attention sublayer with residual.

    ``return_kv=True`` additionally returns the rotated K and V panels —
    prefill writes them straight into the decode cache.
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, h, positions)
    o = _sdpa(cfg, q, k, v, causal=causal, window=window)
    y = x + o @ p["wo"]
    if return_kv:
        return y, k, v
    return y


def attention_decode(
    cfg: LMConfig,
    p: dict,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cache_pos: jax.Array,
    index: jax.Array,
    *,
    window: int = 0,
):
    """One-token decode against a ring-buffer KV cache.

    cache_[kv]: [b, W, kvh, hd] where W = min(max_len, window or max_len);
    cache_pos: [W] absolute positions stored per slot (-1 = empty).
    With full attention W = max_len and the ring degenerates to the usual
    append cache; with a sliding window (zamba2 long-context) it is a true
    ring — this is how ``long_500k`` decodes with a 4096-slot cache.
    Returns (y, cache_k', cache_v', cache_pos').
    """
    b = x.shape[0]
    W = cache_k.shape[1]
    pos = jnp.full((b, 1), index, jnp.int32)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, h, pos)
    slot = jnp.asarray(index % W, jnp.int32)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, slot, 0, 0))
    cache_pos = jax.lax.dynamic_update_slice(cache_pos, pos[:1, 0], (slot,))
    seen = (cache_pos >= 0) & (cache_pos <= index)
    if window:
        seen &= cache_pos > index - window
    d = cfg.hd
    group = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, 1, cfg.n_kv_heads, group, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, cache_k.astype(q.dtype)).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    scores = jnp.where(seen[None, None, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, cache_v.astype(q.dtype)).reshape(b, 1, cfg.q_dim)
    return x + o @ p["wo"], cache_k, cache_v, cache_pos


def cross_attention(cfg: LMConfig, p: dict, x: jax.Array, enc_out: jax.Array) -> jax.Array:
    """Encoder-decoder cross attention (whisper). No RoPE on cross-KV."""
    b, s, _ = x.shape
    se = enc_out.shape[1]
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.hd)
    k = (enc_out @ p["wk"]).reshape(b, se, cfg.n_kv_heads, cfg.hd)
    v = (enc_out @ p["wv"]).reshape(b, se, cfg.n_kv_heads, cfg.hd)
    o = _sdpa(cfg, q, k, v, causal=False)
    return x + o @ p["wo"]


# ---------------------------------------------------------------------------
# FFN (dense + MoE)
# ---------------------------------------------------------------------------


def dense_ffn(cfg: LMConfig, p: dict, x: jax.Array) -> jax.Array:
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.ffn_kind == "relu2":
        u = jax.nn.relu(h @ p["w_in"])
        return x + (u * u) @ p["w_out"]  # squared-ReLU (nemotron)
    g = jax.nn.silu(h @ p["w_gate"])
    u = h @ p["w_up"]
    return x + (g * u) @ p["w_down"]


def moe_capacity(cfg: LMConfig, tokens_local: int) -> int:
    cap = math.ceil(tokens_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)  # round up to a multiple of 8


def moe_ffn_local(cfg: LMConfig, p: dict, x: jax.Array, capacity: int) -> tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE with per-shard capacity, sort-free dispatch.

    x: [b_local, s, d] — tokens of ONE data shard.  Expert weights carry the
    full expert axis; their d_ff axis may be TP-sharded by the caller (the
    psum then happens outside).  Returns (y_partial, aux_loss).
    """
    b, s, dm = x.shape
    E, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = x.reshape(t, dm)
    logits = xf.astype(jnp.float32) @ p["router"]  # [t, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, k)  # [t, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch-style)
    me = probs.mean(0)
    ce = jnp.zeros((E,)).at[expert.reshape(-1)].add(1.0) / (t * k)
    aux = E * jnp.sum(me * ce)

    flat_e = expert.reshape(-1)  # [t*k], grouped by token
    flat_tok = jnp.repeat(jnp.arange(t), k)
    flat_gate = gate.reshape(-1)
    # position of each (token, expert) pair within its expert's queue
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(t * k), flat_e]
    keep = pos < capacity
    slot = jnp.where(keep, flat_e * capacity + pos, E * capacity)  # overflow -> scratch row
    scale = keep.astype(x.dtype)[:, None]
    buf = (
        jnp.zeros((E * capacity + 1, dm), x.dtype)
        .at[slot]
        .add(xf[flat_tok] * scale, mode="drop")
    )
    xe = buf[:-1].reshape(E, capacity, dm)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["we_gate"]))
    u = jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    ye = jnp.einsum("ecf,efd->ecd", g * u, p["we_down"]).reshape(E * capacity, dm)
    contrib = ye[jnp.where(keep, slot, 0)] * (flat_gate.astype(x.dtype)[:, None] * scale)
    y = jnp.zeros((t, dm), x.dtype).at[flat_tok].add(contrib)
    if cfg.n_shared_experts:
        h = xf
        gs = jax.nn.silu(h @ p["ws_gate"])
        us = h @ p["ws_up"]
        y = y + (gs * us) @ p["ws_down"]
    return y.reshape(b, s, dm), aux


def moe_ffn(cfg: LMConfig, p: dict, x: jax.Array, mesh=None, dp_axes=("data",), tp_axis="model"):
    """MoE sublayer with residual.  With a mesh: shard_map local dispatch +
    TP psum; without: plain local computation (single-device smoke tests)."""
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if mesh is None:
        y, aux = moe_ffn_local(cfg, p, h, moe_capacity(cfg, h.shape[0] * h.shape[1]))
        return x + y, aux

    from jax.sharding import PartitionSpec as P

    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    tokens_local = (x.shape[0] // dp) * x.shape[1]
    capacity = moe_capacity(cfg, tokens_local)

    w_specs = {
        "router": P(None, None),
        "we_gate": P(None, None, tp_axis),
        "we_up": P(None, None, tp_axis),
        "we_down": P(None, tp_axis, None),
        "ln2": P(None),
    }
    if cfg.n_shared_experts:
        w_specs.update(ws_gate=P(None, tp_axis), ws_up=P(None, tp_axis), ws_down=P(tp_axis, None))
    used = {k: p[k] for k in w_specs}

    def local_fn(h_loc, w):
        y, aux = moe_ffn_local(cfg, w, h_loc, capacity)
        y = jax.lax.psum(y, tp_axis)
        aux = jax.lax.pmean(aux, dp_axes)
        return y, aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(dp_axes, None, None), w_specs),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False,
    )(h, used)
    return x + y, aux


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality, arXiv:2405.21060 minimal formulation)
# ---------------------------------------------------------------------------


def _segsum(a: jax.Array) -> jax.Array:
    """a: [..., cl] log-decays -> [..., cl, cl] lower-tri cumulative sums."""
    cl = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((cl, cl), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int, return_state: bool = False, unroll: bool = False):
    """Chunked SSD scan.

    x: [b, l, h, p]   dt: [b, l, h]   A: [h] (negative)
    B, C: [b, l, n]   (n_groups = 1: B/C shared across heads)
    Returns y: [b, l, h, p] (+ final state [b, h, p, n] if requested).
    l must be a multiple of ``chunk``.
    """
    b, l, h, pdim = x.shape
    n = B.shape[-1]
    c = l // chunk
    a = (dt * A).astype(jnp.float32)  # [b, l, h] log decay
    xdt = x * dt[..., None].astype(x.dtype)

    a_c = a.reshape(b, c, chunk, h).transpose(0, 1, 3, 2)  # [b,c,h,cl]
    x_c = xdt.reshape(b, c, chunk, h, pdim)
    B_c = B.reshape(b, c, chunk, n)
    C_c = C.reshape(b, c, chunk, n)

    # intra-chunk (quadratic within chunk)
    Lmat = jnp.exp(_segsum(a_c)).astype(x.dtype)  # [b,c,h,cl,cl]
    G = jnp.einsum("bcln,bcsn->bcls", C_c, B_c)  # [b,c,cl,cl]
    y_diag = jnp.einsum("bcls,bchls,bcshp->bclhp", G, Lmat, x_c)

    # chunk states
    a_cum = jnp.cumsum(a_c, axis=-1)  # [b,c,h,cl]
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum).astype(x.dtype)
    S_c = jnp.einsum("bcln,bchl,bclhp->bchpn", B_c, decay_states, x_c)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(a_cum[..., -1])  # [b,c,h] fp32
    def step(h_prev, inp):
        S, dec = inp
        return h_prev * dec[..., None, None].astype(h_prev.dtype) + S, h_prev

    S_swap = jnp.moveaxis(S_c, 1, 0)  # [c,b,h,p,n]
    dec_swap = jnp.moveaxis(chunk_decay, 1, 0)  # [c,b,h]
    final_state, H_in = jax.lax.scan(step, jnp.zeros_like(S_swap[0]), (S_swap, dec_swap), unroll=unroll)
    H_in = jnp.moveaxis(H_in, 0, 1)  # [b,c,h,p,n] state entering each chunk

    in_decay = jnp.exp(a_cum).astype(x.dtype)  # [b,c,h,cl]
    y_off = jnp.einsum("bcln,bchl,bchpn->bclhp", C_c, in_decay, H_in)
    y = (y_diag + y_off).reshape(b, l, h, pdim)
    if return_state:
        return y, final_state
    return y


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv, x: [b, l, ch], w: [K, ch]."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    segs = [xp[:, i : i + x.shape[1], :] * w[i] for i in range(K)]
    return sum(segs)


def ssd_block(cfg: LMConfig, p: dict, x: jax.Array, return_state: bool = False):
    """Mamba2 block (full sequence) with residual.

    ``return_state=True`` also returns (ssm_state [b,h,p,n],
    conv_tail [b,3,di+2n]) for prefill -> decode handoff.
    """
    b, s, d = x.shape
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    hin = rms_norm(x, p["ln"], cfg.norm_eps)
    zxbcdt = hin @ p["in_proj"]
    z, xbc_raw, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc_raw, p["conv_w"]))
    xs, B, C = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # [b,s,h]
    A = -jnp.exp(p["A_log"])  # [h]
    xh = cstr_heads(xs.reshape(b, s, h, cfg.ssm_head_dim), 2)
    res = ssd_chunked(xh, dt, A, B, C, cfg.ssm_chunk, return_state=return_state)
    y, state = res if return_state else (res, None)
    y = y + xh * p["D"][None, None, :, None].astype(x.dtype)
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    y = rms_norm(y, p["gate_ln"], cfg.norm_eps)
    out = x + y @ p["out_proj"]
    if return_state:
        return out, state.astype(x.dtype), xbc_raw[:, -3:, :]
    return out


def ssd_decode(cfg: LMConfig, p: dict, x: jax.Array, ssm_state: jax.Array, conv_state: jax.Array):
    """One-token SSD decode.

    x: [b, 1, d]; ssm_state: [b, h, p, n]; conv_state: [b, K-1, di+2n].
    Returns (y, ssm_state', conv_state').
    """
    b = x.shape[0]
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    hin = rms_norm(x, p["ln"], cfg.norm_eps)
    zxbcdt = hin @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    # conv over [conv_state ; xbc]
    full = jnp.concatenate([conv_state, xbc], axis=1)  # [b, K, ch]
    w = p["conv_w"]  # [K, ch]
    xbc_t = jax.nn.silu(jnp.einsum("bkc,kc->bc", full, w))[:, None, :]
    conv_state = full[:, 1:, :]
    xs, B, C = jnp.split(xbc_t, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])[:, 0]  # [b,h]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)  # [b,h]
    xh = xs.reshape(b, h, cfg.ssm_head_dim)
    dBx = jnp.einsum("bh,bn,bhp->bhpn", dt.astype(x.dtype), B[:, 0], xh)
    ssm_state = ssm_state * dA[..., None, None].astype(x.dtype) + dBx
    y = jnp.einsum("bhpn,bn->bhp", ssm_state, C[:, 0])
    y = y + xh * p["D"][None, :, None].astype(x.dtype)
    y = y.reshape(b, 1, di) * jax.nn.silu(z)
    y = rms_norm(y, p["gate_ln"], cfg.norm_eps)
    return x + y @ p["out_proj"], ssm_state, conv_state
