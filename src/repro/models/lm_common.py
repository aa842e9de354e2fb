"""Shared LM-architecture machinery: config, parameter trees, shardings.

One flexible block zoo covers all 10 assigned architectures:

  * dense GQA transformer (qwen2/3, granite, nemotron, internvl backbone)
    with the per-arch switches the pool requires: qkv_bias (qwen2),
    qk_norm (qwen3), squared-ReLU FFN (nemotron), explicit head_dim
    (qwen3: 128 ≠ d_model/n_heads).
  * MoE transformer (phi3.5-moe top-2/16, llama4-scout top-1/16 + 1 shared
    expert), with a shard_map token-dispatch that keeps MoE FLOPs *active*
    (capacity-based, sort-free local dispatch; see blocks.py).
  * Mamba2 SSD stack (mamba2-130m) and the Zamba2 hybrid (Mamba2 backbone +
    one shared attention block applied every ``shared_attn_every`` layers).
  * Whisper encoder-decoder (audio frontend stubbed to precomputed frame
    embeddings per the assignment).

Parameters are stored **stacked over layers** (`[L, ...]` leading axis) so
the layer loop is a `lax.scan` — the HLO stays small, and remat policy
applies per scan step.

Sharding convention (GSPMD, mesh axes ``("pod", "data", "model")``):
  * batch/sequence activations: batch over ``("pod","data")``.
  * weight matrices: the "feature" dim (d_ff, heads, experts' d_ff, vocab)
    over ``model``; the other big dim over ``data`` (FSDP — re-gathered per
    scan step by XLA).
  * parameters are bf16 with fp32 master copies inside the optimizer
    (see optim/adamw.py) so nemotron-4-340b's optimizer state fits 256×16 GB.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 => d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    ffn_kind: str = "swiglu"  # swiglu | relu2
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    # SSM / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    #: "attn" for pure transformers, "ssd" for mamba2, "hybrid" for zamba2
    block_kind: str = "attn"
    #: hybrid: apply the shared attention block after every k-th SSD layer
    shared_attn_every: int = 6
    # enc-dec (whisper)
    enc_layers: int = 0
    enc_frames: int = 0
    max_decoder_len: int = 0  # whisper caps self-attn context at 448
    # VLM
    n_patches: int = 0  # internvl: patch embeddings prepended (stub frontend)
    sliding_window: int = 0  # 0 => full attention
    attn_q_block: int = 256  # blockwise-attention q-chunk (memory/roofline knob)
    loss_chunk: int = 512  # chunked-xent sequence chunk
    #: unroll every lax.scan, so XLA's cost analysis sees every loop
    #: iteration
    scan_unroll: bool = False
    # --- perf-iteration knobs (§Perf; defaults = paper-faithful baseline) ---
    #: Megatron-style sequence-parallel residual stream (seq over TP)
    sp_residuals: bool = True
    #: keep attention scores/softmax in fp32 (False: bf16 scores)
    attn_fp32_scores: bool = True
    #: gradient-accumulation carry dtype
    accum_dtype: Any = jnp.float32
    #: materialize K/V per q-head (repeat over groups) so attention shards
    #: over all n_heads instead of replicating when n_kv_heads < TP
    attn_repeat_kv: bool = False
    #: decode this many tokens per serve_step call (greedy feedback) —
    #: amortizes the per-call FSDP weight gathers across tokens
    decode_block: int = 1
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: str = "full"  # full | none

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.hd

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.hd

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def param_count(self) -> int:
        """Total parameter count N (for MODEL_FLOPS = 6·N·D accounting)."""
        leaves = jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))
        return sum(math.prod(l.shape) for l in jax.tree.leaves(leaves))

    def active_param_count(self) -> int:
        """Params touched per token (MoE: routed top_k + shared only)."""
        total = self.param_count()
        if not self.is_moe:
            return total
        per_expert = _ffn_param_count(self)
        inactive = (self.n_experts - self.top_k) * per_expert * self.n_layers
        return total - inactive


def _ffn_param_count(cfg: LMConfig) -> int:
    mats = 3 if cfg.ffn_kind == "swiglu" else 2
    return mats * cfg.d_model * cfg.d_ff


# ---------------------------------------------------------------------------
# Initializers (params stacked over layers)
# ---------------------------------------------------------------------------


def _dense(key, shape, scale_dim, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(scale_dim)).astype(dtype)


def _attn_params(cfg: LMConfig, key, n_layers: int, dtype) -> dict:
    ks = jax.random.split(key, 8)
    d, q, kv, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.hd
    L = n_layers
    p = {
        "wq": _dense(ks[0], (L, d, q), d, dtype),
        "wk": _dense(ks[1], (L, d, kv), d, dtype),
        "wv": _dense(ks[2], (L, d, kv), d, dtype),
        "wo": _dense(ks[3], (L, q, d), q, dtype),
        "ln1": jnp.ones((L, d), jnp.float32),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((L, q), dtype)
        p["bk"] = jnp.zeros((L, kv), dtype)
        p["bv"] = jnp.zeros((L, kv), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, hd), jnp.float32)
        p["k_norm"] = jnp.ones((L, hd), jnp.float32)
    return p


def _ffn_params(cfg: LMConfig, key, n_layers: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    d, f, L = cfg.d_model, cfg.d_ff, n_layers
    if cfg.is_moe:
        E = cfg.n_experts
        ke = jax.random.split(ks[0], 3)
        p = {
            "router": _dense(ks[2], (L, d, E), d, jnp.float32),
            "we_gate": _dense(ke[0], (L, E, d, f), d, dtype),
            "we_up": _dense(ke[1], (L, E, d, f), d, dtype),
            "we_down": _dense(ke[2], (L, E, f, d), f, dtype),
            "ln2": jnp.ones((L, d), jnp.float32),
        }
        if cfg.n_shared_experts:
            kss = jax.random.split(ks[1], 3)
            fs = f * cfg.n_shared_experts
            p["ws_gate"] = _dense(kss[0], (L, d, fs), d, dtype)
            p["ws_up"] = _dense(kss[1], (L, d, fs), d, dtype)
            p["ws_down"] = _dense(kss[2], (L, fs, d), f, dtype)
        return p
    if cfg.ffn_kind == "swiglu":
        return {
            "w_gate": _dense(ks[0], (L, d, f), d, dtype),
            "w_up": _dense(jax.random.split(ks[2])[0], (L, d, f), d, dtype),
            "w_down": _dense(ks[1], (L, f, d), f, dtype),
            "ln2": jnp.ones((L, d), jnp.float32),
        }
    if cfg.ffn_kind == "relu2":
        return {
            "w_in": _dense(ks[0], (L, d, f), d, dtype),
            "w_out": _dense(ks[1], (L, f, d), f, dtype),
            "ln2": jnp.ones((L, d), jnp.float32),
        }
    raise ValueError(cfg.ffn_kind)


def _ssd_params(cfg: LMConfig, key, n_layers: int, dtype) -> dict:
    ks = jax.random.split(key, 4)
    d, di, n, h, L = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, n_layers
    # in_proj emits [z (di), x (di), B (n), C (n), dt (h)]
    return {
        "in_proj": _dense(ks[0], (L, d, 2 * di + 2 * n + h), d, dtype),
        "conv_w": _dense(ks[1], (L, 4, di + 2 * n), 4, dtype),  # causal depthwise conv
        "A_log": jnp.zeros((L, h), jnp.float32),
        "D": jnp.ones((L, h), jnp.float32),
        "dt_bias": jnp.zeros((L, h), jnp.float32),
        "out_proj": _dense(ks[2], (L, di, d), di, dtype),
        "ln": jnp.ones((L, d), jnp.float32),
        "gate_ln": jnp.ones((L, di), jnp.float32),
    }


def init_params(cfg: LMConfig, key: jax.Array) -> dict:
    """Full parameter tree for any supported architecture."""
    dtype = cfg.dtype
    keys = jax.random.split(key, 10)
    p: dict[str, Any] = {
        "embed": _dense(keys[0], (cfg.vocab, cfg.d_model), cfg.d_model, dtype),
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "unembed": _dense(keys[1], (cfg.d_model, cfg.vocab), cfg.d_model, dtype),
    }
    if cfg.block_kind == "attn":
        p["blocks"] = {
            **_attn_params(cfg, keys[2], cfg.n_layers, dtype),
            **_ffn_params(cfg, keys[3], cfg.n_layers, dtype),
        }
    elif cfg.block_kind == "ssd":
        p["blocks"] = _ssd_params(cfg, keys[2], cfg.n_layers, dtype)
    elif cfg.block_kind == "hybrid":
        p["blocks"] = _ssd_params(cfg, keys[2], cfg.n_layers, dtype)
        shared_cfg = dataclasses.replace(cfg, qkv_bias=False, qk_norm=False, n_experts=0, ffn_kind="swiglu")
        p["shared"] = {
            **_attn_params(shared_cfg, keys[4], 1, dtype),
            **_ffn_params(shared_cfg, keys[5], 1, dtype),
        }
    else:
        raise ValueError(cfg.block_kind)
    if cfg.is_encdec:
        enc_cfg = dataclasses.replace(cfg, qkv_bias=cfg.qkv_bias, n_experts=0)
        p["enc_blocks"] = {
            **_attn_params(enc_cfg, keys[6], cfg.enc_layers, dtype),
            **_ffn_params(enc_cfg, keys[7], cfg.enc_layers, dtype),
        }
        p["enc_ln_f"] = jnp.ones((cfg.d_model,), jnp.float32)
        # decoder cross-attention (stacked over decoder layers)
        ks = jax.random.split(keys[8], 4)
        d, q, kv = cfg.d_model, cfg.q_dim, cfg.kv_dim
        L = cfg.n_layers
        p["cross"] = {
            "wq": _dense(ks[0], (L, d, q), d, dtype),
            "wk": _dense(ks[1], (L, d, kv), d, dtype),
            "wv": _dense(ks[2], (L, d, kv), d, dtype),
            "wo": _dense(ks[3], (L, q, d), q, dtype),
            "ln": jnp.ones((L, d), jnp.float32),
        }
    if cfg.n_patches:
        p["patch_proj"] = _dense(keys[9], (cfg.d_model, cfg.d_model), cfg.d_model, dtype)
    return p


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------


def param_shardings(cfg: LMConfig, *, fsdp_axis: str | None = "data", tp_axis: str = "model") -> dict:
    """PartitionSpec tree matching init_params' structure.

    TP shards the feature dim; FSDP shards the other matrix dim.  Vectors
    (norm scales, biases) are replicated except long ones sharded on TP.
    """
    f, d = fsdp_axis, tp_axis

    def attn(L_prefix=True):
        sp = {
            "wq": P(None, f, d),
            "wk": P(None, f, d),
            "wv": P(None, f, d),
            "wo": P(None, d, f),
            "ln1": P(None, None),
        }
        if cfg.qkv_bias:
            sp.update(bq=P(None, d), bk=P(None, d), bv=P(None, d))
        if cfg.qk_norm:
            sp.update(q_norm=P(None, None), k_norm=P(None, None))
        return sp

    def ffn():
        if cfg.is_moe:
            sp = {
                "router": P(None, f, None),
                "we_gate": P(None, None, f, d),
                "we_up": P(None, None, f, d),
                "we_down": P(None, None, d, f),
                "ln2": P(None, None),
            }
            if cfg.n_shared_experts:
                sp.update(ws_gate=P(None, f, d), ws_up=P(None, f, d), ws_down=P(None, d, f))
            return sp
        if cfg.ffn_kind == "relu2":
            return {"w_in": P(None, f, d), "w_out": P(None, d, f), "ln2": P(None, None)}
        return {
            "w_gate": P(None, f, d),
            "w_up": P(None, f, d),
            "w_down": P(None, d, f),
            "ln2": P(None, None),
        }

    def ssd():
        return {
            "in_proj": P(None, f, d),
            "conv_w": P(None, None, d),
            "A_log": P(None, None),
            "D": P(None, None),
            "dt_bias": P(None, None),
            "out_proj": P(None, d, f),
            "ln": P(None, None),
            "gate_ln": P(None, d),
        }

    sp: dict[str, Any] = {
        # embed: vocab REPLICATED, d_model TP-sharded — token lookup stays a
        # local gather (vocab-sharded embeddings force an all-gather of the
        # table or one-hot matmuls through the lookup).  unembed: d_model
        # FSDP, vocab TP-sharded — the head matmul emits vocab-sharded
        # logits and the chunked loss reduces over the shard in place.
        "embed": P(None, d),
        "ln_f": P(None),
        "unembed": P(f, d),
    }
    if cfg.block_kind == "attn":
        sp["blocks"] = {**attn(), **ffn()}
    elif cfg.block_kind == "ssd":
        sp["blocks"] = ssd()
    else:  # hybrid
        sp["blocks"] = ssd()
        sp["shared"] = {
            "wq": P(None, f, d),
            "wk": P(None, f, d),
            "wv": P(None, f, d),
            "wo": P(None, d, f),
            "ln1": P(None, None),
            "w_gate": P(None, f, d),
            "w_up": P(None, f, d),
            "w_down": P(None, d, f),
            "ln2": P(None, None),
        }
    if cfg.is_encdec:
        enc_sp = {
            "wq": P(None, f, d),
            "wk": P(None, f, d),
            "wv": P(None, f, d),
            "wo": P(None, d, f),
            "ln1": P(None, None),
            "w_gate": P(None, f, d),
            "w_up": P(None, f, d),
            "w_down": P(None, d, f),
            "ln2": P(None, None),
        }
        if cfg.qkv_bias:
            enc_sp.update(bq=P(None, d), bk=P(None, d), bv=P(None, d))
        sp["enc_blocks"] = enc_sp
        sp["enc_ln_f"] = P(None)
        sp["cross"] = {
            "wq": P(None, f, d),
            "wk": P(None, f, d),
            "wv": P(None, f, d),
            "wo": P(None, d, f),
            "ln": P(None, None),
        }
    if cfg.n_patches:
        sp["patch_proj"] = P(f, d)
    return sp


# ---------------------------------------------------------------------------
# Activation-sharding constraints (GSPMD guard rails)
#
# Without these, XLA's sharding propagation can drift into pathological
# layouts (replicated batch + factor-sharded head dims ⇒ hundred-GiB score
# all-reduces — observed on qwen2, whose 14 heads don't divide TP=16).
# Every layer boundary pins activations to (batch over DP, rest replicated);
# head tensors opt into TP sharding only when the head count divides.
# ---------------------------------------------------------------------------

_DIST: dict = {"mesh": None, "dp": ("data",), "tp": "model", "seq_shard": True}


@contextlib.contextmanager
def dist_context(mesh, dp_axes=("data",), tp_axis: str = "model", seq_shard: bool = True):
    old = dict(_DIST)
    _DIST.update(mesh=mesh, dp=tuple(dp_axes), tp=tp_axis, seq_shard=seq_shard)
    try:
        yield
    finally:
        _DIST.update(old)


def _dp_if_divisible(x, mesh, dp):
    total = 1
    for a in dp:
        total *= mesh.shape[a]
    return dp if x.shape[0] % total == 0 else None


def cstr_act(x: jax.Array) -> jax.Array:
    """Pin [batch, seq, ...] activations: batch over DP, seq over TP.

    Sequence-sharding the residual stream over the ``model`` axis is
    Megatron-style sequence parallelism: remat-saved per-layer residuals
    shrink by the TP extent (nemotron-4-340b: 232 GiB -> 14.5 GiB per
    device), paid for with the per-layer all-gather/reduce-scatter pair XLA
    inserts around the TP matmuls.  Falls back to replicated seq when the
    length doesn't divide (whisper's 1500 frames).
    """
    mesh = _DIST["mesh"]
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = _dp_if_divisible(x, mesh, _DIST["dp"])
    tp = _DIST["tp"]
    seq = tp if (_DIST["seq_shard"] and x.ndim >= 3 and x.shape[1] % mesh.shape[tp] == 0) else None
    rest = [None] * (x.ndim - 2)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, seq, *rest) if x.ndim >= 2 else P(dp))
    )


def cstr_heads(x: jax.Array, head_axis: int) -> jax.Array:
    """Pin [batch, ..., heads, ...]: batch over DP, heads over TP if divisible."""
    return cstr_custom(x, batch_axis=0, tp_axis_at=head_axis)


def cstr_custom(x: jax.Array, *, batch_axis: int | None = None, tp_axis_at: int | None = None) -> jax.Array:
    """Pin arbitrary axes: DP at ``batch_axis``, TP at ``tp_axis_at`` —
    both only when the axis length divides the mesh extent."""
    mesh = _DIST["mesh"]
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    parts: list = [None] * x.ndim
    if batch_axis is not None:
        dp = _DIST["dp"]
        total = 1
        for a in dp:
            total *= mesh.shape[a]
        if x.shape[batch_axis] % total == 0:
            parts[batch_axis] = dp
    tp = _DIST["tp"]
    if tp_axis_at is not None and x.shape[tp_axis_at] % mesh.shape[tp] == 0:
        parts[tp_axis_at] = tp
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


# ---------------------------------------------------------------------------
# Small shared ops
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rotary(x: jax.Array, positions: jax.Array, base: float = 10_000.0) -> jax.Array:
    """Apply RoPE.  x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(base) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
