"""Model assembly: forward / loss / train / decode for every architecture.

One entry point per phase, uniform across the 10 assigned architectures:

  * ``train_loss(cfg, params, batch, mesh)``   — full fwd + chunked xent.
  * ``make_train_step(cfg, opt, mesh)``        — loss + grad + AdamW update.
  * ``init_cache(cfg, batch, max_len)``        — decode-state pytree.
  * ``make_serve_step(cfg, mesh)``             — one-token decode.
  * ``prefill(cfg, params, batch, cache)``     — encoder pass / KV warmup.

The layer loop is `lax.scan` over `[L, ...]`-stacked params; remat is a
`jax.checkpoint` around the scan body (policy: save the per-layer residual
stream only).  Hybrid (zamba2) runs an outer scan over groups of
``shared_attn_every`` SSD layers with the shared attention block applied
between groups.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from . import blocks
from .lm_common import LMConfig, cstr_act, dist_context, init_params, param_shardings, rms_norm


def _scan(cfg: LMConfig, f, init, xs):
    """lax.scan that fully unrolls under cfg.scan_unroll (cost-analysis mode)."""
    return jax.lax.scan(f, init, xs, unroll=bool(cfg.scan_unroll))

# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(cfg: LMConfig, params: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["embed"], tokens, axis=0)


def lm_head_loss(cfg: LMConfig, params: dict, h: jax.Array, labels: jax.Array, mask: jax.Array) -> jax.Array:
    """Chunked softmax cross-entropy: never materializes [B, S, V] at once."""
    b, s, d = h.shape
    cs = s
    for cand in (cfg.loss_chunk, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if s % cand == 0:
            cs = cand
            break
    n_chunks = s // cs
    hc = h.reshape(b, n_chunks, cs, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, n_chunks, cs).transpose(1, 0, 2)
    mc = mask.reshape(b, n_chunks, cs).transpose(1, 0, 2)

    def body(acc, inp):
        hh, yy, mm = inp
        hh = cstr_act(hh)
        logits = (hh @ params["unembed"]).astype(jnp.float32)
        # reductions over the (TP-sharded) vocab axis partition cleanly;
        # the gold logit is a one-hot contraction — a take_along_axis here
        # would force XLA to all-gather the full logits chunk.
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
        onehot = jax.nn.one_hot(yy, logits.shape[-1], dtype=logits.dtype)
        gold = jnp.sum(logits * onehot, axis=-1)
        nll = (logz - gold) * mm
        return acc + nll.sum(), None

    # checkpoint: backward re-computes each logits chunk instead of saving
    # n_chunks × [B, cs, V] residuals (the whole point of chunking).
    total, _ = _scan(cfg, jax.checkpoint(body, prevent_cse=False), jnp.zeros((), jnp.float32), (hc, yc, mc))
    return total / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Backbones (full sequence)
# ---------------------------------------------------------------------------


def _attn_block_fwd(cfg: LMConfig, lp: dict, x: jax.Array, positions, mesh, dp_axes, tp_axis):
    x = blocks.attention(cfg, lp, x, positions, causal=True, window=cfg.sliding_window)
    if cfg.is_moe:
        x, aux = blocks.moe_ffn(cfg, lp, x, mesh, dp_axes, tp_axis)
    else:
        x, aux = blocks.dense_ffn(cfg, lp, x), jnp.zeros(())
    return x, aux


def _maybe_remat(cfg: LMConfig, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    return fn


def backbone(
    cfg: LMConfig,
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    mesh=None,
    dp_axes=("data",),
    tp_axis: str = "model",
) -> tuple[jax.Array, jax.Array]:
    """Run the layer stack on embedded inputs x. Returns (h, aux_loss)."""
    if cfg.block_kind == "attn":

        def body(carry, lp):
            h, aux = carry
            h = cstr_act(h)
            h, a = _attn_block_fwd(cfg, lp, h, positions, mesh, dp_axes, tp_axis)
            return (cstr_act(h), aux + a), None

        (x, aux), _ = _scan(cfg, _maybe_remat(cfg, body), (x, jnp.zeros(())), params["blocks"])
        return rms_norm(x, params["ln_f"], cfg.norm_eps), aux

    if cfg.block_kind == "ssd":

        def body(carry, lp):
            (h,) = carry
            return (cstr_act(blocks.ssd_block(cfg, lp, cstr_act(h))),), None

        (x,), _ = _scan(cfg, _maybe_remat(cfg, body), (x,), params["blocks"])
        return rms_norm(x, params["ln_f"], cfg.norm_eps), jnp.zeros(())

    if cfg.block_kind == "hybrid":
        k = cfg.shared_attn_every
        n_groups = cfg.n_layers // k
        grouped = jax.tree.map(lambda a: a.reshape(n_groups, k, *a.shape[1:]), params["blocks"])
        shared = jax.tree.map(lambda a: a[0], params["shared"])  # strip L=1

        def inner(carry, lp):
            (h,) = carry
            return (cstr_act(blocks.ssd_block(cfg, lp, cstr_act(h))),), None

        def group_body(carry, group_params):
            (h,) = carry
            (h,), _ = _scan(cfg, _maybe_remat(cfg, inner), (h,), group_params)
            h = blocks.attention(cfg, shared, h, positions, causal=True, window=cfg.sliding_window)
            h = blocks.dense_ffn(
                dataclasses.replace(cfg, ffn_kind="swiglu", n_experts=0), shared, h
            )
            return (h,), None

        (x,), _ = _scan(cfg, group_body, (x,), grouped)
        return rms_norm(x, params["ln_f"], cfg.norm_eps), jnp.zeros(())

    raise ValueError(cfg.block_kind)


def encoder(cfg: LMConfig, params: dict, frames: jax.Array) -> jax.Array:
    """Whisper encoder: bidirectional attention over (stubbed) frame embeds."""
    positions = jnp.arange(frames.shape[1])[None, :] * jnp.ones((frames.shape[0], 1), jnp.int32)
    enc_cfg = dataclasses.replace(cfg, n_experts=0, ffn_kind="swiglu", sliding_window=0)

    def body(carry, lp):
        (h,) = carry
        h = blocks.attention(enc_cfg, lp, cstr_act(h), positions, causal=False)
        h = blocks.dense_ffn(enc_cfg, lp, h)
        return (cstr_act(h),), None

    (h,), _ = _scan(cfg, _maybe_remat(cfg, body), (frames,), params["enc_blocks"])
    return rms_norm(h, params["enc_ln_f"], cfg.norm_eps)


def decoder_with_cross(
    cfg: LMConfig, params: dict, x: jax.Array, positions: jax.Array, enc_out: jax.Array
) -> jax.Array:
    def body(carry, lps):
        (h,) = carry
        lp, cp = lps
        h = blocks.attention(cfg, lp, cstr_act(h), positions, causal=True)
        h = blocks.cross_attention(cfg, cp, h, enc_out)
        h = blocks.dense_ffn(cfg, lp, h)
        return (cstr_act(h),), None

    (h,), _ = _scan(cfg, _maybe_remat(cfg, body), (x,), (params["blocks"], params["cross"]))
    return rms_norm(h, params["ln_f"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def train_loss(cfg: LMConfig, params: dict, batch: dict, mesh=None, dp_axes=("data",), tp_axis="model") -> jax.Array:
    """Next-token loss for any architecture family."""
    with dist_context(mesh, dp_axes, tp_axis, seq_shard=cfg.sp_residuals):
        return _train_loss(cfg, params, batch, mesh, dp_axes, tp_axis)


def _train_loss(cfg, params, batch, mesh, dp_axes, tp_axis):
    if cfg.is_encdec:
        enc_out = encoder(cfg, params, batch["frames"].astype(cfg.dtype))
        tokens = batch["tokens"]
        x = embed_tokens(cfg, params, tokens)
        positions = jnp.arange(tokens.shape[1])[None, :] * jnp.ones((tokens.shape[0], 1), jnp.int32)
        h = decoder_with_cross(cfg, params, x, positions, enc_out)
        return lm_head_loss(cfg, params, h, batch["labels"], batch["labels"] >= 0)

    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    if cfg.n_patches:
        patches = batch["patch_embeds"].astype(cfg.dtype) @ params["patch_proj"]
        x = jnp.concatenate([patches, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :] * jnp.ones((b, 1), jnp.int32)
    h, aux = backbone(cfg, params, x, positions, mesh, dp_axes, tp_axis)
    if cfg.n_patches:
        h = h[:, cfg.n_patches :, :]
    loss = lm_head_loss(cfg, params, h, batch["labels"], batch["labels"] >= 0)
    return loss + 0.01 * aux


def make_train_step(cfg: LMConfig, optimizer, mesh=None, dp_axes=("data",), tp_axis="model", accum: int = 1):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``accum > 1`` splits the global batch into that many microbatches and
    accumulates fp32 gradients under lax.scan — the standard way to fit
    activation memory for the multi-hundred-B train cells.
    """

    def loss_fn(p, b):
        return train_loss(cfg, p, b, mesh, dp_axes, tp_axis)

    def train_step(params, opt_state, batch):
        if accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), batch
            )
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, cfg.accum_dtype), params)

            def body(carry, mbatch):
                gsum, lsum = carry
                l, g = jax.value_and_grad(loss_fn)(params, mbatch)
                gsum = jax.tree.map(lambda a, b: a + b.astype(cfg.accum_dtype), gsum, g)
                return (gsum, lsum + l), None

            (grads, loss), _ = _scan(cfg, body, (g0, jnp.zeros(())), mb)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss / accum
        params, opt_state, om = optimizer.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, **om}

    return train_step


# ---------------------------------------------------------------------------
# Decoding / serving
# ---------------------------------------------------------------------------


def init_cache(cfg: LMConfig, batch: int, max_len: int) -> dict:
    """Decode-state pytree. Ring KV for attention; SSM state for SSD."""
    cache: dict[str, Any] = {"index": jnp.zeros((), jnp.int32)}
    W = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    if cfg.block_kind == "attn":
        L = cfg.n_layers
        cache["k"] = jnp.zeros((L, batch, W, cfg.n_kv_heads, cfg.hd), cfg.dtype)
        cache["v"] = jnp.zeros((L, batch, W, cfg.n_kv_heads, cfg.hd), cfg.dtype)
        cache["pos"] = -jnp.ones((L, W), jnp.int32)
    elif cfg.block_kind in ("ssd", "hybrid"):
        L = cfg.n_layers
        cache["ssm"] = jnp.zeros((L, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), cfg.dtype)
        cache["conv"] = jnp.zeros((L, batch, 3, cfg.d_inner + 2 * cfg.ssm_state), cfg.dtype)
        if cfg.block_kind == "hybrid":
            g = cfg.n_layers // cfg.shared_attn_every
            cache["shared_k"] = jnp.zeros((g, batch, W, cfg.n_kv_heads, cfg.hd), cfg.dtype)
            cache["shared_v"] = jnp.zeros((g, batch, W, cfg.n_kv_heads, cfg.hd), cfg.dtype)
            cache["shared_pos"] = -jnp.ones((g, W), jnp.int32)
    if cfg.is_encdec:
        # decoder self-attn ring (W capped at whisper's 448) + cross K/V set at prefill
        Wd = min(max_len, cfg.max_decoder_len or max_len)
        L = cfg.n_layers
        cache["k"] = jnp.zeros((L, batch, Wd, cfg.n_kv_heads, cfg.hd), cfg.dtype)
        cache["v"] = jnp.zeros((L, batch, Wd, cfg.n_kv_heads, cfg.hd), cfg.dtype)
        cache["pos"] = -jnp.ones((L, Wd), jnp.int32)
        cache["cross_k"] = jnp.zeros((L, batch, cfg.enc_frames, cfg.n_kv_heads, cfg.hd), cfg.dtype)
        cache["cross_v"] = jnp.zeros((L, batch, cfg.enc_frames, cfg.n_kv_heads, cfg.hd), cfg.dtype)
    return cache


def prefill(cfg: LMConfig, params: dict, batch: dict, cache: dict) -> dict:
    """Encoder pass + cross-KV warmup (enc-dec only; LM prefill = train fwd)."""
    if not cfg.is_encdec:
        return cache
    enc_out = encoder(cfg, params, batch["frames"].astype(cfg.dtype))
    b, se, _ = enc_out.shape

    def per_layer(cp):
        k = (enc_out @ cp["wk"]).reshape(b, se, cfg.n_kv_heads, cfg.hd)
        v = (enc_out @ cp["wv"]).reshape(b, se, cfg.n_kv_heads, cfg.hd)
        return k, v

    k, v = jax.vmap(per_layer)(params["cross"])
    return {**cache, "cross_k": k, "cross_v": v}


def serve_step(cfg: LMConfig, params: dict, cache: dict, tokens: jax.Array, mesh=None, dp_axes=("data",), tp_axis="model"):
    """Decode one token.  tokens: [b, 1] -> (logits [b, vocab], cache')."""
    with dist_context(mesh, dp_axes, tp_axis, seq_shard=cfg.sp_residuals):
        return _serve_step(cfg, params, cache, tokens, mesh, dp_axes, tp_axis)


def _serve_step(cfg, params, cache, tokens, mesh, dp_axes, tp_axis):
    index = cache["index"]
    x = embed_tokens(cfg, params, tokens)

    if cfg.is_encdec:

        def body(h, inp):
            lp, cp, ck, cv, cpos, xk, xv = inp
            h, ck, cv, cpos = blocks.attention_decode(cfg, lp, h, ck, cv, cpos, index)
            # cross attention against prefilled encoder KV
            hq = rms_norm(h, cp["ln"], cfg.norm_eps)
            b = h.shape[0]
            q = (hq @ cp["wq"]).reshape(b, 1, cfg.n_heads, cfg.hd)
            o = blocks._sdpa(cfg, q, xk, xv, causal=False)
            h = h + o @ cp["wo"]
            h = blocks.dense_ffn(cfg, lp, h)
            return h, (ck, cv, cpos)

        x, (k2, v2, p2) = _scan(cfg, 
            body,
            x,
            (params["blocks"], params["cross"], cache["k"], cache["v"], cache["pos"], cache["cross_k"], cache["cross_v"]),
        )
        cache = {**cache, "k": k2, "v": v2, "pos": p2, "index": index + 1}

    elif cfg.block_kind == "attn":

        def body(h, inp):
            lp, ck, cv, cpos = inp
            h, ck, cv, cpos = blocks.attention_decode(
                cfg, lp, h, ck, cv, cpos, index, window=cfg.sliding_window
            )
            if cfg.is_moe:
                h2, _ = blocks.moe_ffn(cfg, lp, h, mesh, dp_axes, tp_axis)
            else:
                h2 = blocks.dense_ffn(cfg, lp, h)
            return h2, (ck, cv, cpos)

        x, (k2, v2, p2) = _scan(cfg, body, x, (params["blocks"], cache["k"], cache["v"], cache["pos"]))
        cache = {**cache, "k": k2, "v": v2, "pos": p2, "index": index + 1}

    elif cfg.block_kind == "ssd":

        def body(h, inp):
            lp, ssm, conv = inp
            h, ssm, conv = blocks.ssd_decode(cfg, lp, h, ssm, conv)
            return h, (ssm, conv)

        x, (ssm2, conv2) = _scan(cfg, body, x, (params["blocks"], cache["ssm"], cache["conv"]))
        cache = {**cache, "ssm": ssm2, "conv": conv2, "index": index + 1}

    elif cfg.block_kind == "hybrid":
        k = cfg.shared_attn_every
        n_groups = cfg.n_layers // k
        grouped = jax.tree.map(lambda a: a.reshape(n_groups, k, *a.shape[1:]), params["blocks"])
        shared = jax.tree.map(lambda a: a[0], params["shared"])
        g_ssm = cache["ssm"].reshape(n_groups, k, *cache["ssm"].shape[1:])
        g_conv = cache["conv"].reshape(n_groups, k, *cache["conv"].shape[1:])

        def inner(h, inp):
            lp, ssm, conv = inp
            h, ssm, conv = blocks.ssd_decode(cfg, lp, h, ssm, conv)
            return h, (ssm, conv)

        def group_body(h, inp):
            gp, ssm, conv, sk, sv, spos = inp
            h, (ssm2, conv2) = _scan(cfg, inner, h, (gp, ssm, conv))
            h, sk, sv, spos = blocks.attention_decode(
                cfg, shared, h, sk, sv, spos, index, window=cfg.sliding_window
            )
            h = blocks.dense_ffn(dataclasses.replace(cfg, ffn_kind="swiglu", n_experts=0), shared, h)
            return h, (ssm2, conv2, sk, sv, spos)

        x, (ssm2, conv2, sk2, sv2, sp2) = _scan(cfg, 
            group_body,
            x,
            (grouped, g_ssm, g_conv, cache["shared_k"], cache["shared_v"], cache["shared_pos"]),
        )
        cache = {
            **cache,
            "ssm": ssm2.reshape(cache["ssm"].shape),
            "conv": conv2.reshape(cache["conv"].shape),
            "shared_k": sk2,
            "shared_v": sv2,
            "shared_pos": sp2,
            "index": index + 1,
        }
    else:
        raise ValueError(cfg.block_kind)

    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = (h[:, 0, :] @ params["unembed"]).astype(jnp.float32)
    return logits, cache


def make_serve_step(cfg: LMConfig, mesh=None, dp_axes=("data",), tp_axis="model"):
    return partial(serve_step, cfg, mesh=mesh, dp_axes=dp_axes, tp_axis=tp_axis)


def serve_block(cfg: LMConfig, params: dict, cache: dict, tokens: jax.Array, mesh=None, dp_axes=("data",), tp_axis="model"):
    """Decode ``cfg.decode_block`` tokens in one call (greedy feedback).

    One jit invocation = one pass of FSDP weight gathers amortized over the
    whole block — the §Perf fix for collective-bound decode cells.  Returns
    (logits of the LAST token, cache).
    """
    k = cfg.decode_block
    if k <= 1:
        return serve_step(cfg, params, cache, tokens, mesh, dp_axes, tp_axis)

    def body(carry, _):
        tok, cache = carry
        logits, cache = serve_step(cfg, params, cache, tok, mesh, dp_axes, tp_axis)
        nxt = jnp.argmax(logits, axis=-1)[:, None].astype(tok.dtype)
        return (nxt, cache), None

    (tok, cache), _ = _scan(cfg, body, (tokens, cache), jnp.arange(k - 1))
    logits, cache = serve_step(cfg, params, cache, tok, mesh, dp_axes, tp_axis)
    return logits, cache


# ---------------------------------------------------------------------------
# Prefill: full-sequence forward that also materializes the decode cache
# ---------------------------------------------------------------------------


def prefill_step(cfg: LMConfig, params: dict, batch: dict, mesh=None, dp_axes=("data",), tp_axis="model", max_len: int | None = None):
    """Serving prefill: forward over the prompt, emitting the decode cache.

    Returns (last-token logits [b, vocab], cache).  The cache layout matches
    ``init_cache(cfg, b, seq)`` so decode can continue from it directly.
    """
    with dist_context(mesh, dp_axes, tp_axis, seq_shard=cfg.sp_residuals):
        return _prefill_step(cfg, params, batch, mesh, dp_axes, tp_axis, max_len)


def _prefill_step(cfg, params, batch, mesh, dp_axes, tp_axis, max_len=None):
    if cfg.is_encdec:
        # whisper: encode + cross-KV, then prefill the (capped) decoder prompt
        cache = init_cache(cfg, batch["tokens"].shape[0], cfg.max_decoder_len)
        cache = prefill(cfg, params, batch, cache)
        tokens = batch["tokens"][:, : cfg.max_decoder_len]
        x = embed_tokens(cfg, params, tokens)
        b, s, _ = x.shape
        positions = jnp.arange(s)[None, :] * jnp.ones((b, 1), jnp.int32)
        enc_out = encoder(cfg, params, batch["frames"].astype(cfg.dtype))

        def body(carry, lps):
            (h,) = carry
            lp, cp = lps
            h, k, v = blocks.attention(cfg, lp, h, positions, causal=True, return_kv=True)
            h = blocks.cross_attention(cfg, cp, h, enc_out)
            h = blocks.dense_ffn(cfg, lp, h)
            return (h,), (k, v)

        (h,), (ks, vs) = _scan(cfg, _maybe_remat(cfg, body), (x,), (params["blocks"], params["cross"]))
        h = rms_norm(h, params["ln_f"], cfg.norm_eps)
        W = cache["k"].shape[2]
        cache = {
            **cache,
            "k": jnp.zeros_like(cache["k"]).at[:, :, :s].set(ks[:, :, :W].astype(cfg.dtype)),
            "v": jnp.zeros_like(cache["v"]).at[:, :, :s].set(vs[:, :, :W].astype(cfg.dtype)),
            "pos": jnp.where(jnp.arange(W)[None, :] < s, jnp.arange(W)[None, :], -1)
            * jnp.ones((cfg.n_layers, 1), jnp.int32),
            "index": jnp.asarray(s, jnp.int32),
        }
        logits = (h[:, -1, :] @ params["unembed"]).astype(jnp.float32)
        return logits, cache

    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    if cfg.n_patches:
        patches = batch["patch_embeds"].astype(cfg.dtype) @ params["patch_proj"]
        x = jnp.concatenate([patches, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :] * jnp.ones((b, 1), jnp.int32)
    pos_row = jnp.arange(s, dtype=jnp.int32)

    if cfg.block_kind == "attn":

        def body(carry, lp):
            (h,) = carry
            h, k, v = blocks.attention(
                cfg, lp, h, positions, causal=True, window=cfg.sliding_window, return_kv=True
            )
            if cfg.is_moe:
                h, _ = blocks.moe_ffn(cfg, lp, h, mesh, dp_axes, tp_axis)
            else:
                h = blocks.dense_ffn(cfg, lp, h)
            return (h,), (k.astype(cfg.dtype), v.astype(cfg.dtype))

        (h,), (ks, vs) = _scan(cfg, _maybe_remat(cfg, body), (x,), params["blocks"])
        W = max(max_len or s, s)
        if W > s:  # leave room for decode continuation
            pad = ((0, 0), (0, 0), (0, W - s), (0, 0), (0, 0))
            ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
        pos = jnp.where(jnp.arange(W) < s, jnp.arange(W), -1)
        cache = {
            "k": ks,
            "v": vs,
            "pos": jnp.broadcast_to(pos[None, :], (cfg.n_layers, W)).astype(jnp.int32),
            "index": jnp.asarray(s, jnp.int32),
        }
    elif cfg.block_kind == "ssd":

        def body(carry, lp):
            (h,) = carry
            h, state, conv_tail = blocks.ssd_block(cfg, lp, h, return_state=True)
            return (h,), (state, conv_tail)

        (h,), (ssm, conv) = _scan(cfg, _maybe_remat(cfg, body), (x,), params["blocks"])
        cache = {"ssm": ssm, "conv": conv, "index": jnp.asarray(s, jnp.int32)}
    elif cfg.block_kind == "hybrid":
        k_every = cfg.shared_attn_every
        n_groups = cfg.n_layers // k_every
        grouped = jax.tree.map(lambda a: a.reshape(n_groups, k_every, *a.shape[1:]), params["blocks"])
        shared = jax.tree.map(lambda a: a[0], params["shared"])

        def inner(carry, lp):
            (h,) = carry
            h, state, conv_tail = blocks.ssd_block(cfg, lp, h, return_state=True)
            return (h,), (state, conv_tail)

        def group_body(carry, gp):
            (h,) = carry
            (h,), (ssm, conv) = _scan(cfg, _maybe_remat(cfg, inner), (h,), gp)
            h, sk, sv = blocks.attention(
                cfg, shared, h, positions, causal=True, window=cfg.sliding_window, return_kv=True
            )
            h = blocks.dense_ffn(dataclasses.replace(cfg, ffn_kind="swiglu", n_experts=0), shared, h)
            return (h,), (ssm, conv, sk.astype(cfg.dtype), sv.astype(cfg.dtype))

        (h,), (ssm, conv, sks, svs) = _scan(cfg, group_body, (x,), grouped)
        W = min(s, cfg.sliding_window) if cfg.sliding_window else s
        # ring layout: slot = pos % W; for prefill keep the LAST W positions
        sel = pos_row[-W:]
        slots = sel % W
        sk_ring = jnp.zeros((n_groups, b, W, cfg.n_kv_heads, cfg.hd), cfg.dtype).at[:, :, slots].set(sks[:, :, -W:])
        sv_ring = jnp.zeros((n_groups, b, W, cfg.n_kv_heads, cfg.hd), cfg.dtype).at[:, :, slots].set(svs[:, :, -W:])
        spos = -jnp.ones((n_groups, W), jnp.int32)
        spos = spos.at[:, slots].set(jnp.broadcast_to(sel[None, :], (n_groups, W)))
        cache = {
            "ssm": ssm.reshape(cfg.n_layers, b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            "conv": conv.reshape(cfg.n_layers, b, 3, cfg.d_inner + 2 * cfg.ssm_state),
            "shared_k": sk_ring,
            "shared_v": sv_ring,
            "shared_pos": spos,
            "index": jnp.asarray(s, jnp.int32),
        }
    else:
        raise ValueError(cfg.block_kind)

    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    logits = (h[:, -1, :] @ params["unembed"]).astype(jnp.float32)
    return logits, cache


# ---------------------------------------------------------------------------
# Shisha integration: per-layer static costs (generalized Eq. 1)
# ---------------------------------------------------------------------------


def layer_costs(cfg: LMConfig, seq: int, batch: int = 1):
    """Per-block cost Layers for the scheduler (DESIGN.md §4)."""
    from ..core.cost_model import Layer, attention_layer, ffn_layer, fuse, ssd_layer

    out: list[Layer] = []
    if cfg.is_encdec:
        for i in range(cfg.enc_layers):
            a = attention_layer(f"enc{i}.attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.enc_frames, batch=batch)
            f = ffn_layer(f"enc{i}.ffn", cfg.d_model, cfg.d_ff, seq=cfg.enc_frames, batch=batch)
            out.append(fuse(f"enc{i}", [a, f]))
        dec_len = min(seq, cfg.max_decoder_len or seq)
        for i in range(cfg.n_layers):
            a = attention_layer(f"dec{i}.attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, dec_len, batch=batch)
            c = attention_layer(f"dec{i}.cross", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.enc_frames, batch=batch)
            f = ffn_layer(f"dec{i}.ffn", cfg.d_model, cfg.d_ff, seq=dec_len, batch=batch)
            out.append(fuse(f"dec{i}", [a, c, f]))
        return out
    if cfg.block_kind == "attn":
        for i in range(cfg.n_layers):
            a = attention_layer(
                f"blk{i}.attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, seq, batch=batch,
                window=cfg.sliding_window or None,
            )
            f = ffn_layer(
                f"blk{i}.ffn", cfg.d_model, cfg.d_ff, seq=seq, batch=batch,
                gated=cfg.ffn_kind == "swiglu",
                n_experts=cfg.n_experts, top_k=cfg.top_k,
            )
            out.append(fuse(f"blk{i}", [a, f], kind="moe" if cfg.is_moe else "block"))
        return out
    # ssd / hybrid
    for i in range(cfg.n_layers):
        s = ssd_layer(f"blk{i}.ssd", cfg.d_model, cfg.ssm_state, seq=seq, batch=batch, expand=cfg.ssm_expand)
        if cfg.block_kind == "hybrid" and (i + 1) % cfg.shared_attn_every == 0:
            a = attention_layer(
                f"blk{i}.shared_attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads, seq, batch=batch,
                window=cfg.sliding_window or None,
            )
            f = ffn_layer(f"blk{i}.shared_ffn", cfg.d_model, cfg.d_ff, seq=seq, batch=batch)
            out.append(fuse(f"blk{i}", [s, a, f], kind="hybrid"))
        else:
            out.append(s)
    return out
