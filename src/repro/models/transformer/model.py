"""Transformer LM: init + train/prefill/decode forwards for all 5 assigned archs.

Layers are stacked and executed under ``jax.lax.scan`` (O(1)-layer HLO: the
512-device dry-run compiles in seconds; the roofline analyzer multiplies
while-body costs by the trip count). Remat policy wraps the scan body.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.transformer.attention import (
    attention_seq_parallel, blocked_attention, decode_attention_sharded,
    mla_decode_attention_sharded,
)
from repro.models.transformer.config import TransformerConfig
from repro.models.transformer.layers import (
    apply_rope, ffn, init_ffn, init_rmsnorm, rmsnorm, softcap,
)
from repro.models.transformer.moe import init_moe, moe_ffn
from repro.sharding import L, Rules, shard_act, stack_dims


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Mesh + axis naming used by shard_map sub-blocks and act constraints."""
    mesh: Optional[Mesh]
    batch_axes: Tuple[str, ...] = ("data",)
    rules: Rules = dataclasses.field(default_factory=dict)

    @staticmethod
    def single_device() -> "ParallelCtx":
        # Auto axes: JAX 0.9's make_mesh defaults to Explicit, which puts
        # the mesh into every array's type and breaks the layer scan
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        return ParallelCtx(mesh=mesh, batch_axes=("data",), rules={})


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: TransformerConfig, dtype):
    d, hq, hkv, hd = cfg.d_model, cfg.n_q, cfg.n_kv, cfg.head_dim
    ks = jax.random.split(key, 8)
    s = d ** -0.5
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "wq_a": L(jax.random.normal(ks[0], (d, m.q_lora), dtype) * s, ("embed", "q_lora")),
            "q_norm": init_rmsnorm(m.q_lora, jnp.float32) | {},
            "wq_b": L(jax.random.normal(ks[1], (m.q_lora, hq, m.qk_dim), dtype) * m.q_lora ** -0.5,
                      ("q_lora", "heads", "head_dim")),
            "wkv_a": L(jax.random.normal(ks[2], (d, m.kv_lora + m.qk_rope), dtype) * s,
                       ("embed", "kv_lora")),
            "kv_norm": init_rmsnorm(m.kv_lora, jnp.float32),
            "wk_b": L(jax.random.normal(ks[3], (m.kv_lora, hq, m.qk_nope), dtype) * m.kv_lora ** -0.5,
                      ("kv_lora", "heads", "head_dim")),
            "wv_b": L(jax.random.normal(ks[4], (m.kv_lora, hq, m.v_dim), dtype) * m.kv_lora ** -0.5,
                      ("kv_lora", "heads", "head_dim")),
            "wo": L(jax.random.normal(ks[5], (hq, m.v_dim, d), dtype) * (hq * m.v_dim) ** -0.5,
                    ("heads", "head_dim", "embed")),
        }
    return {
        "wq": L(jax.random.normal(ks[0], (d, hq, hd), dtype) * s, ("embed", "heads", "head_dim")),
        "wk": L(jax.random.normal(ks[1], (d, hkv, hd), dtype) * s, ("embed", "kv_heads", "head_dim")),
        "wv": L(jax.random.normal(ks[2], (d, hkv, hd), dtype) * s, ("embed", "kv_heads", "head_dim")),
        "wo": L(jax.random.normal(ks[3], (hq, hd, d), dtype) * (hq * hd) ** -0.5,
                ("heads", "head_dim", "embed")),
    }


def _init_layer(key, cfg: TransformerConfig, moe_layer: bool, dense_ff: int):
    ka, kf, ksh = jax.random.split(key, 3)
    dtype = cfg.param_dtype
    p = {
        "attn": _init_attn(ka, cfg, dtype),
        "ln_attn_pre": init_rmsnorm(cfg.d_model),
        "ln_mlp_pre": init_rmsnorm(cfg.d_model),
    }
    if cfg.post_norms:
        p["ln_attn_post"] = init_rmsnorm(cfg.d_model)
        p["ln_mlp_post"] = init_rmsnorm(cfg.d_model)
    if moe_layer:
        p["moe"] = init_moe(kf, cfg.d_model, cfg.moe, cfg.mlp_variant, dtype)
        if cfg.moe.n_shared:
            p["shared"] = init_ffn(ksh, cfg.d_model, cfg.moe.d_ff * cfg.moe.n_shared,
                                   cfg.mlp_variant, dtype)
    else:
        p["ffn"] = init_ffn(kf, cfg.d_model, dense_ff, cfg.mlp_variant, dtype)
    return p


def init_transformer(key, cfg: TransformerConfig):
    """Returns a tree of L leaves (use sharding.split_tree to get params+specs)."""
    k_emb, k_lay, k_dense, k_un = jax.random.split(key, 4)
    dtype = cfg.param_dtype
    n_dense = cfg.moe.first_dense_layers if cfg.moe else 0
    n_scan = cfg.n_layers - n_dense

    layer_init = functools.partial(_init_layer, cfg=cfg,
                                   moe_layer=cfg.moe is not None,
                                   dense_ff=cfg.d_ff)
    layers = jax.vmap(layer_init)(jax.random.split(k_lay, n_scan))
    layers = stack_dims("layers", layers)

    p = {
        "embed": L(jax.random.normal(k_emb, (cfg.vocab, cfg.d_model), dtype)
                   * cfg.d_model ** -0.5, ("vocab", "embed")),
        "layers": layers,
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if n_dense:
        dense_init = functools.partial(_init_layer, cfg=cfg, moe_layer=False,
                                       dense_ff=cfg.moe.first_dense_ff or cfg.d_ff)
        dense = jax.vmap(dense_init)(jax.random.split(k_dense, n_dense))
        p["dense_layers"] = stack_dims("layers", dense)
    if not cfg.tied_embeddings:
        p["unembed"] = L(jax.random.normal(k_un, (cfg.d_model, cfg.vocab), dtype)
                         * cfg.d_model ** -0.5, ("embed", "vocab"))
    return p


# ---------------------------------------------------------------------------
# attention blocks (train/prefill)
# ---------------------------------------------------------------------------

def _qkv_gqa(p, x, cfg: TransformerConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _qkv_mla(p, x, cfg: TransformerConfig, positions):
    m = cfg.mla
    ql = rmsnorm(p["q_norm"], x @ p["wq_a"], cfg.norm_eps)
    q = jnp.einsum("bsl,lhk->bshk", ql, p["wq_b"])            # [B,S,H,qk_dim]
    q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv = x @ p["wkv_a"]                                        # [B,S,kv_lora+rope]
    ckv = rmsnorm(p["kv_norm"], kv[..., :m.kv_lora], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, m.kv_lora:], positions, cfg.rope_theta)
    k_nope = jnp.einsum("bsl,lhk->bshk", ckv, p["wk_b"])
    v = jnp.einsum("bsl,lhk->bshk", ckv, p["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (m.qk_rope,))],
                        axis=-1)
    return q, k, v, ckv, k_rope[:, :, 0]


def attn_block(p, x, cfg: TransformerConfig, ctx: ParallelCtx, window):
    B, S, D = x.shape
    positions = jnp.arange(S)[None]
    if cfg.mla is not None:
        q, k, v, _, _ = _qkv_mla(p, x, cfg, positions)
        scale = cfg.mla.qk_dim ** -0.5
    else:
        q, k, v = _qkv_gqa(p, x, cfg, positions)
        scale = cfg.head_dim ** -0.5
        vd = cfg.head_dim

    multi_model = ctx.mesh is not None and ctx.mesh.shape.get("model", 1) > 1
    if cfg.attn_parallel == "ring" and multi_model:
        from repro.models.transformer.ring_attention import ring_attention
        out = ring_attention(q, k, v, ctx.mesh, ctx.batch_axes, scale=scale,
                             causal=True, window=window, softcap=cfg.attn_softcap,
                             q_block=cfg.q_block, kv_block=cfg.kv_block)
    elif cfg.attn_parallel == "seq" and multi_model:
        out = attention_seq_parallel(q, k, v, ctx.mesh, ctx.batch_axes,
                                     scale=scale, causal=True, window=window,
                                     softcap=cfg.attn_softcap,
                                     q_block=cfg.q_block, kv_block=cfg.kv_block)
    else:
        q = shard_act(q, ("act_batch", None, "act_heads", None), ctx.rules, ctx.mesh)
        k = shard_act(k, ("act_batch", None, "act_kv_heads", None), ctx.rules, ctx.mesh)
        v = shard_act(v, ("act_batch", None, "act_kv_heads", None), ctx.rules, ctx.mesh)
        out = blocked_attention(q, k, v, scale=scale, causal=True, window=window,
                                softcap=cfg.attn_softcap,
                                q_block=cfg.q_block, kv_block=cfg.kv_block)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# layer + model forward (train/score)
# ---------------------------------------------------------------------------

def _ffn_block(p_l, h, cfg, ctx):
    if "moe" in p_l:
        fsdp = ctx.rules.get("embed")
        fsdp = fsdp if isinstance(fsdp, str) and ctx.mesh is not None \
            and ctx.mesh.shape.get(fsdp, 1) > 1 else None
        f, aux = moe_ffn(p_l["moe"], h, cfg.moe, cfg.mlp_variant, ctx.mesh,
                         ctx.batch_axes, fsdp_axis=fsdp)
        if "shared" in p_l:
            f = f + ffn(p_l["shared"], h, cfg.mlp_variant)
    else:
        f, aux = ffn(p_l["ffn"], h, cfg.mlp_variant), jnp.zeros((), jnp.float32)
    return f, aux


def layer_fn(p_l, x, window, cfg: TransformerConfig, ctx: ParallelCtx):
    h = rmsnorm(p_l["ln_attn_pre"], x, cfg.norm_eps)
    a = attn_block(p_l["attn"], h, cfg, ctx, window)
    if cfg.post_norms:
        a = rmsnorm(p_l["ln_attn_post"], a, cfg.norm_eps)
    x = x + a
    x = shard_act(x, ("act_batch", None, None), ctx.rules, ctx.mesh)
    h = rmsnorm(p_l["ln_mlp_pre"], x, cfg.norm_eps)
    f, aux = _ffn_block(p_l, h, cfg, ctx)
    if cfg.post_norms:
        f = rmsnorm(p_l["ln_mlp_post"], f, cfg.norm_eps)
    x = x + f
    x = shard_act(x, ("act_batch", None, None), ctx.rules, ctx.mesh)
    return x, aux


def _remat(fn, cfg: TransformerConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "full": save nothing


def _scan_layers(stack, x, cfg, ctx, windows):
    body = _remat(lambda xc, p_w: layer_fn(p_w[0], xc, p_w[1], cfg, ctx), cfg)

    def step(xc, p_w):
        xn, aux = body(xc, p_w)
        return xn, aux

    x, auxs = jax.lax.scan(step, x, (stack, windows))
    return x, auxs.sum()


def forward(params, tokens, cfg: TransformerConfig, ctx: ParallelCtx):
    """tokens [B,S] -> logits [B,S,V] (+ MoE aux loss)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.gemma_norm:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = shard_act(x, ("act_batch", None, None), ctx.rules, ctx.mesh)

    windows = jnp.asarray(cfg.layer_windows, jnp.int32)
    aux = jnp.zeros((), jnp.float32)
    n_dense = cfg.moe.first_dense_layers if cfg.moe else 0
    if n_dense:
        x, a0 = _scan_layers(params["dense_layers"], x, cfg, ctx, windows[:n_dense])
        aux += a0
    x, a1 = _scan_layers(params["layers"], x, cfg, ctx, windows[n_dense:])
    aux += a1

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    un = params["embed"].T if cfg.tied_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", x, un)
    logits = softcap(logits, cfg.final_softcap)
    logits = shard_act(logits, ("act_batch", None, "act_vocab"), ctx.rules, ctx.mesh)
    return logits, aux


def lm_loss(params, tokens, targets, cfg: TransformerConfig, ctx: ParallelCtx,
            z_coef: float = 1e-4):
    logits, aux = forward(params, tokens, cfg, ctx)
    logits = logits.astype(jnp.float32)
    z = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = (z - ll).mean()
    zloss = z_coef * jnp.square(z).mean()
    moe_aux = (cfg.moe.aux_coef * aux / cfg.n_layers) if cfg.moe else 0.0
    return ce + zloss + moe_aux, {"ce": ce, "z": zloss}


# ---------------------------------------------------------------------------
# KV cache: init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, capacity: int, dtype=None):
    dtype = dtype or cfg.cache_dtype
    n_scan = cfg.n_layers - (cfg.moe.first_dense_layers if cfg.moe else 0)
    n_dense = cfg.n_layers - n_scan
    def mk(n):
        if cfg.mla is not None:
            return {
                "ckv": jnp.zeros((n, batch, capacity, cfg.mla.kv_lora), dtype),
                "krope": jnp.zeros((n, batch, capacity, cfg.mla.qk_rope), dtype),
            }
        return {
            "k": jnp.zeros((n, batch, capacity, cfg.n_kv, cfg.head_dim), dtype),
            "v": jnp.zeros((n, batch, capacity, cfg.n_kv, cfg.head_dim), dtype),
        }
    cache = {"layers": mk(n_scan)}
    if n_dense:
        cache["dense_layers"] = mk(n_dense)
    return cache


def cache_specs(cfg: TransformerConfig, ctx: ParallelCtx, batch: int):
    """PartitionSpecs for the cache pytree (seq dim sharded for decode)."""
    seq = cfg.seq_shard_decode
    b_axes = ctx.batch_axes if batch > 1 else None
    def mk():
        if cfg.mla is not None:
            return {"ckv": P(None, b_axes, seq, None), "krope": P(None, b_axes, seq, None)}
        return {"k": P(None, b_axes, seq, None, None), "v": P(None, b_axes, seq, None, None)}
    out = {"layers": mk()}
    if cfg.moe and cfg.moe.first_dense_layers:
        out["dense_layers"] = mk()
    return out


def _decode_layer(p_l, x, cache_l, cache_len, window, cfg, ctx):
    """x: [B,1,D]; cache_l: per-layer cache slice. Returns (x', cache_l')."""
    B = x.shape[0]
    h = rmsnorm(p_l["ln_attn_pre"], x, cfg.norm_eps)
    positions = jnp.full((B, 1), cache_len)
    seq_axes = cfg.seq_shard_decode
    if cfg.mla is not None:
        m = cfg.mla
        ql = rmsnorm(p_l["attn"]["q_norm"], h @ p_l["attn"]["wq_a"], cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", ql, p_l["attn"]["wq_b"])
        q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)[:, 0]
        q_lat = jnp.einsum("bhk,lhk->bhl", q_nope[:, 0], p_l["attn"]["wk_b"])
        kv = h @ p_l["attn"]["wkv_a"]
        ckv_new = rmsnorm(p_l["attn"]["kv_norm"], kv[..., :m.kv_lora], cfg.norm_eps)[:, 0]
        krope_new = apply_rope(kv[..., None, m.kv_lora:], positions, cfg.rope_theta)[:, 0, 0]
        out_lat, ckv, krope = mla_decode_attention_sharded(
            q_lat.astype(x.dtype), q_rope.astype(x.dtype),
            cache_l["ckv"], cache_l["krope"],
            ckv_new.astype(cache_l["ckv"].dtype), krope_new.astype(cache_l["krope"].dtype),
            cache_len, ctx.mesh, ctx.batch_axes, seq_axes, scale=m.qk_dim ** -0.5)
        out = jnp.einsum("bhl,lhk->bhk", out_lat, p_l["attn"]["wv_b"])
        a = jnp.einsum("bhk,hkd->bd", out, p_l["attn"]["wo"])[:, None]
        new_cache = {"ckv": ckv, "krope": krope}
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, p_l["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, p_l["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p_l["attn"]["wv"])
        q = apply_rope(q, positions, cfg.rope_theta)[:, 0]
        k = apply_rope(k, positions, cfg.rope_theta)[:, 0]
        out, kc, vc = decode_attention_sharded(
            q, cache_l["k"], cache_l["v"], k.astype(cache_l["k"].dtype),
            v[:, 0].astype(cache_l["v"].dtype), cache_len,
            ctx.mesh, ctx.batch_axes, seq_axes,
            scale=cfg.head_dim ** -0.5, window=window, softcap=cfg.attn_softcap)
        a = jnp.einsum("bhk,hkd->bd", out, p_l["attn"]["wo"])[:, None]
        new_cache = {"k": kc, "v": vc}
    if cfg.post_norms:
        a = rmsnorm(p_l["ln_attn_post"], a, cfg.norm_eps)
    x = x + a
    h = rmsnorm(p_l["ln_mlp_pre"], x, cfg.norm_eps)
    f, _ = _ffn_block(p_l, h, cfg, ctx)
    if cfg.post_norms:
        f = rmsnorm(p_l["ln_mlp_post"], f, cfg.norm_eps)
    return x + f, new_cache


def decode_step(params, cache, tokens, cache_len, cfg: TransformerConfig, ctx: ParallelCtx):
    """One decode step: tokens [B,1] + cache -> (logits [B,1,V], cache')."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.gemma_norm:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    windows = jnp.asarray(cfg.layer_windows, jnp.int32)
    n_dense = cfg.moe.first_dense_layers if cfg.moe else 0

    def scan_group(stack, cache_g, x, wins):
        def step(xc, pw_cache):
            p_l, w, c_l = pw_cache
            xn, c_new = _decode_layer(p_l, xc, c_l, cache_len, w, cfg, ctx)
            return xn, c_new
        x, new_cache = jax.lax.scan(step, x, (stack, wins, cache_g))
        return x, new_cache

    new_cache = {}
    if n_dense:
        x, nc = scan_group(params["dense_layers"], cache["dense_layers"], x, windows[:n_dense])
        new_cache["dense_layers"] = nc
    x, nc = scan_group(params["layers"], cache["layers"], x, windows[n_dense:])
    new_cache["layers"] = nc

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    un = params["embed"].T if cfg.tied_embeddings else params["unembed"]
    logits = softcap(jnp.einsum("bsd,dv->bsv", x, un), cfg.final_softcap)
    return logits, new_cache


def prefill_step(params, tokens, cfg: TransformerConfig, ctx: ParallelCtx,
                 capacity: Optional[int] = None):
    """tokens [B,S] -> (last-position logits [B,V], cache at len S).

    Runs the blocked train-style forward; K/V (or MLA latents) per layer are
    collected as scan outputs, padded to cache capacity.
    """
    B, S = tokens.shape
    capacity = capacity or S
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.gemma_norm:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = shard_act(x, ("act_batch", None, None), ctx.rules, ctx.mesh)
    positions = jnp.arange(S)[None]
    windows = jnp.asarray(cfg.layer_windows, jnp.int32)
    n_dense = cfg.moe.first_dense_layers if cfg.moe else 0

    def layer_with_cache(p_l, xc, w):
        h = rmsnorm(p_l["ln_attn_pre"], xc, cfg.norm_eps)
        if cfg.mla is not None:
            q, k, v, ckv, krope = _qkv_mla(p_l["attn"], h, cfg, positions)
            scale = cfg.mla.qk_dim ** -0.5
            cache_out = {
                "ckv": jnp.pad(ckv, ((0, 0), (0, capacity - S), (0, 0))).astype(cfg.cache_dtype),
                "krope": jnp.pad(krope, ((0, 0), (0, capacity - S), (0, 0))).astype(cfg.cache_dtype),
            }
        else:
            q, k, v = _qkv_gqa(p_l["attn"], h, cfg, positions)
            scale = cfg.head_dim ** -0.5
            cache_out = {
                "k": jnp.pad(k, ((0, 0), (0, capacity - S), (0, 0), (0, 0))).astype(cfg.cache_dtype),
                "v": jnp.pad(v, ((0, 0), (0, capacity - S), (0, 0), (0, 0))).astype(cfg.cache_dtype),
            }
        if cfg.attn_parallel == "seq" and ctx.mesh is not None and ctx.mesh.shape.get("model", 1) > 1:
            out = attention_seq_parallel(q, k, v, ctx.mesh, ctx.batch_axes, scale=scale,
                                         causal=True, window=w, softcap=cfg.attn_softcap,
                                         q_block=cfg.q_block, kv_block=cfg.kv_block)
        else:
            out = blocked_attention(q, k, v, scale=scale, causal=True, window=w,
                                    softcap=cfg.attn_softcap,
                                    q_block=cfg.q_block, kv_block=cfg.kv_block)
        a = jnp.einsum("bshk,hkd->bsd", out, p_l["attn"]["wo"])
        if cfg.post_norms:
            a = rmsnorm(p_l["ln_attn_post"], a, cfg.norm_eps)
        xc = xc + a
        h2 = rmsnorm(p_l["ln_mlp_pre"], xc, cfg.norm_eps)
        f, _ = _ffn_block(p_l, h2, cfg, ctx)
        if cfg.post_norms:
            f = rmsnorm(p_l["ln_mlp_post"], f, cfg.norm_eps)
        return xc + f, cache_out

    def scan_group(stack, x, wins):
        body = _remat(lambda xc, pw: layer_with_cache(pw[0], xc, pw[1]), cfg)
        return jax.lax.scan(lambda xc, pw: body(xc, pw), x, (stack, wins))

    cache = {}
    if n_dense:
        x, c0 = scan_group(params["dense_layers"], x, windows[:n_dense])
        cache["dense_layers"] = c0
    x, c1 = scan_group(params["layers"], x, windows[n_dense:])
    cache["layers"] = c1

    x = rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    un = params["embed"].T if cfg.tied_embeddings else params["unembed"]
    logits = softcap(jnp.einsum("bsd,dv->bsv", x, un), cfg.final_softcap)
    return logits[:, 0], cache
