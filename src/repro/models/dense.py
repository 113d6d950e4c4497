"""Decoder-only transformer: dense (llama/qwen-style GQA), MoE (mixtral/
olmoe), and VLM backbone (stub patch embeddings prepended).

Layers are applied with ``jax.lax.scan`` over stacked params so HLO size is
O(1) in depth. ``cfg.remat`` wraps the layer body in ``jax.checkpoint``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .api import BaseModel, register_family
from .attention import (attention, cache_prefill, init_kv_cache,
                        paged_gather, paged_scatter_pages,
                        paged_write_slots, suffix_attend)
from .common import (ArchConfig, KeyGen, apply_rope, dense_init, dt,
                     embed_init, ones_init, rmsnorm, softmax_xent, zeros_init)
from .moe import init_moe, moe_ffn
from ..sharding import shard_act

BATCH = ("pod", "data")


def _init_layer(key, cfg: ArchConfig, dtype):
    kg = KeyGen(key)
    D, H, KV, dh, F = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh, cfg.d_ff
    p = {
        "ln1": jnp.ones((D,), jnp.float32),
        "ln2": jnp.ones((D,), jnp.float32),
        "wq": dense_init(kg(), (D, H * dh), dtype),
        "wk": dense_init(kg(), (D, KV * dh), dtype),
        "wv": dense_init(kg(), (D, KV * dh), dtype),
        "wo": dense_init(kg(), (H * dh, D), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((KV * dh,), dtype)
        p["bv"] = jnp.zeros((KV * dh,), dtype)
    if cfg.n_experts:
        p["moe"] = init_moe(kg(), cfg, dtype)
    else:
        p["mlp"] = {
            "w_gate": dense_init(kg(), (D, F), dtype),
            "w_up": dense_init(kg(), (D, F), dtype),
            "w_down": dense_init(kg(), (F, D), dtype),
        }
    return p


def _qkv(h, lp, cfg: ArchConfig, positions):
    B, S, D = h.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, KV, dh)
    v = v.reshape(B, S, KV, dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_act(q, (BATCH, None, "model", None))
    k = shard_act(k, (BATCH, None, "model", None))
    return q, k, v


def _ffn(h, lp, cfg: ArchConfig, dropless: bool = False):
    if cfg.n_experts:
        return moe_ffn(lp["moe"], h, cfg, dropless)
    mp = lp["mlp"]
    g = jax.nn.silu(h @ mp["w_gate"])
    u = h @ mp["w_up"]
    y = (g * u) @ mp["w_down"]
    return y, jnp.float32(0.0)


def _layer_full(x, lp, cfg: ArchConfig, positions):
    """Full-sequence layer (train / prefill). Returns (x, (k, v), aux)."""
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = _qkv(h, lp, cfg, positions)
    o = attention(q, k, v, q_pos=positions, kv_pos=positions,
                  window=cfg.sliding_window, chunk=cfg.attn_chunk)
    B, S = x.shape[:2]
    x = x + (o.reshape(B, S, -1) @ lp["wo"]).astype(x.dtype)
    h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    y, aux = _ffn(h2, lp, cfg)
    x = x + y.astype(x.dtype)
    # sequence parallelism: between TP blocks the residual stream is
    # sharded along seq over `model` (Korthikanti et al.) — GSPMD turns the
    # Megatron all-reduces into reduce-scatter + all-gather pairs and the
    # per-device activation footprint drops by the model-axis size
    x = shard_act(x, (BATCH, "model" if cfg.seq_parallel else None, None))
    return x, (k, v), aux


def _layer_suffix(x, lp, cfg: ArchConfig, positions, pk, pv, offset):
    """Suffix-prefill layer: queries at absolute `positions` attend over
    the gathered prefix KV (positions 0..offset-1) plus the suffix's own
    KV. Returns (x, (k, v)) where k, v cover only the suffix slice."""
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = _qkv(h, lp, cfg, positions)
    o = suffix_attend(q, k, v, pk, pv, offset=offset,
                      window=cfg.sliding_window, chunk=cfg.attn_chunk)
    B, S = x.shape[:2]
    x = x + (o.reshape(B, S, -1) @ lp["wo"]).astype(x.dtype)
    h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    y, _ = _ffn(h2, lp, cfg)
    x = x + y.astype(x.dtype)
    x = shard_act(x, (BATCH, "model" if cfg.seq_parallel else None, None))
    return x, (k, v)


def _layer_decode(x, lp, layer_cache, cfg: ArchConfig, pos_scalar):
    """Single-token layer. layer_cache: {k, v} slices + shared pos/t.
    ``pos_scalar`` is the query position — () shared across rows (plain
    decode) or (B,) per-row; either way the math is elementwise-
    identical per row."""
    q_pos = pos_scalar[..., None]         # (1,) shared or (B, 1) per-row
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k1, v1 = _qkv(h, lp, cfg, q_pos)
    new_k, new_v, kv_pos = layer_cache["update"](k1, v1)
    o = attention(q, new_k, new_v, q_pos=q_pos, kv_pos=kv_pos,
                  window=cfg.sliding_window, chunk=0)
    B = x.shape[0]
    x = x + (o.reshape(B, 1, -1) @ lp["wo"]).astype(x.dtype)
    h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    y, _ = _ffn(h2, lp, cfg, dropless=True)
    return x + y.astype(x.dtype), (new_k, new_v)


def _layer_verify(x, lp, layer_cache, cfg: ArchConfig, q_pos):
    """Speculative-verify layer: a width-K+1 causal pass over the live
    cache. x: (B, K1, D); ``q_pos``: (B, K1) per-row absolute positions
    of the window tokens. The whole window's KV lands in the cache
    *before* attention and the per-row position mask (kv_pos <= q_pos_i)
    restricts each query to exactly the key set the chained decode
    would have seen — this is what makes verification one dispatch of
    ~one decode-step's wall cost instead of K+1 sequential steps."""
    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q, k1, v1 = _qkv(h, lp, cfg, q_pos)
    new_k, new_v, kv_pos = layer_cache["update"](k1, v1)
    o = attention(q, new_k, new_v, q_pos=q_pos, kv_pos=kv_pos,
                  window=cfg.sliding_window, chunk=0)
    B, S = x.shape[:2]
    x = x + (o.reshape(B, S, -1) @ lp["wo"]).astype(x.dtype)
    h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    y, _ = _ffn(h2, lp, cfg, dropless=True)
    return x + y.astype(x.dtype), (new_k, new_v)


@register_family("dense")
@register_family("moe")
@register_family("vlm")
class DecoderLM(BaseModel):
    """Dense / MoE / VLM-backbone decoder-only LM."""

    def init(self, rng):
        cfg = self.cfg
        dtype = dt(cfg.param_dtype)
        kg = KeyGen(rng)
        keys = jax.random.split(kg(), cfg.n_layers)
        layers = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(keys)
        params = {
            "embed": embed_init(kg(), (cfg.padded_vocab, cfg.d_model), dtype),
            "layers": layers,
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = dense_init(
                kg(), (cfg.d_model, cfg.padded_vocab), dtype)
        return params

    # ------------------------------------------------------------------
    def _embed(self, params, batch):
        cfg = self.cfg
        x = params["embed"][batch["tokens"]].astype(dt(cfg.compute_dtype))
        if cfg.n_stub_embeds and "stub_embeds" in batch:
            stub = batch["stub_embeds"].astype(x.dtype)
            x = jnp.concatenate([stub, x], axis=1)
        return shard_act(x, (BATCH, "model" if cfg.seq_parallel else None,
                             None))

    def _unembed(self, params, x):
        w = (params["embed"].T if self.cfg.tie_embeddings
             else params["unembed"])
        return x @ w.astype(x.dtype)

    def _run_layers(self, params, x, positions):
        cfg = self.cfg

        def body(carry, lp):
            x, aux = carry
            x, kv, a = _layer_full(x, lp, cfg, positions)
            return (x, aux + a), kv

        if cfg.remat:
            body = jax.checkpoint(body)
        (x, aux), kvs = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                     params["layers"])
        return x, aux, kvs

    # ------------------------------------------------------------------
    def _forward(self, params, batch):
        cfg = self.cfg
        x = self._embed(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S)
        x, aux, _ = self._run_layers(params, x, positions)
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        if cfg.n_stub_embeds:  # logits only at text positions
            x = x[:, cfg.n_stub_embeds:]
        return self._unembed(params, x), aux

    def logits(self, params, batch):
        """Teacher-forced logits at every text position: (B, S, V)."""
        return self._forward(params, batch)[0]

    def loss(self, params, batch):
        cfg = self.cfg
        logits, aux = self._forward(params, batch)
        ce = softmax_xent(logits, batch["labels"])
        total = ce + 0.01 * aux / max(cfg.n_layers, 1)
        return total, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------------
    def init_cache(self, batch_size, capacity):
        cfg = self.cfg
        c = init_kv_cache(batch_size, capacity, cfg.n_kv_heads, cfg.dh,
                          dt(cfg.compute_dtype))
        L = cfg.n_layers
        return {
            "k": jnp.zeros((L,) + c["k"].shape, c["k"].dtype),
            "v": jnp.zeros((L,) + c["v"].shape, c["v"].dtype),
            "pos": c["pos"],
            "t": c["t"],
        }

    def prefill(self, params, batch, capacity=None):
        cfg = self.cfg
        x = self._embed(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S)
        x, _, kvs = self._run_layers(params, x, positions)
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = self._unembed(params, x[:, -1])
        # build cache from stacked per-layer (k, v)
        ks, vs = kvs
        C = capacity or self.cache_capacity(S)
        base = init_kv_cache(x.shape[0], C, cfg.n_kv_heads, cfg.dh,
                             dt(cfg.compute_dtype))
        filled = jax.vmap(lambda k, v: cache_prefill(base, k, v))(ks, vs)
        cache = {"k": filled["k"], "v": filled["v"],
                 "pos": filled["pos"][0], "t": filled["t"][0]}
        return logits, cache

    def decode(self, params, cache, batch):
        cfg = self.cfg
        x = self._embed(params, {"tokens": batch["token"]})
        t = cache["t"]
        C = cache["k"].shape[2]
        slot = t % C

        def body(x, inp):
            lp, ck, cv = inp

            def update(k1, v1):
                nk = jax.lax.dynamic_update_slice(
                    ck, k1.astype(ck.dtype), (0, slot, 0, 0))
                nv = jax.lax.dynamic_update_slice(
                    cv, v1.astype(cv.dtype), (0, slot, 0, 0))
                kv_pos = jax.lax.dynamic_update_slice(
                    cache["pos"], t[None], (slot,))
                return nk, nv, kv_pos

            x, (nk, nv) = _layer_decode(
                x, lp, {"update": update}, cfg, t)
            return x, (nk, nv)

        x, (nks, nvs) = jax.lax.scan(body, x,
                                     (params["layers"], cache["k"], cache["v"]))
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = self._unembed(params, x[:, 0])
        new_cache = {
            "k": nks, "v": nvs,
            "pos": jax.lax.dynamic_update_slice(cache["pos"], t[None], (slot,)),
            "t": t + 1,
        }
        return logits, new_cache

    # ------------------------------------------------------------------
    # Speculative verify: the whole K+1 token window scored in ONE
    # parallel causal pass — this is the mechanism that makes
    # speculation pay: K+1 positions cost roughly one decode step of
    # wall time (width-K1 matmuls against the same weights) instead of
    # K+1 sequential steps. Exactness: all K+1 keys/values land in the
    # cache ring ROPE'd at their absolute positions before attention,
    # and the per-row position mask (kv_pos <= q_pos_i, kv_pos >= 0)
    # gives query i exactly the key set a chained one-by-one decode
    # would have seen; masked slots contribute *exactly* zero (score
    # NEG_INF -> softmax weight 0.0 in f32, and 0 * finite garbage = 0
    # — the written KV values are finite projections of valid/clamped
    # token embeddings, never inf/NaN). Bitwise token identity against
    # the chained decode ladder is asserted by the differential suite
    # (tests/test_speculative.py) on the CPU platform CI pins.
    # ------------------------------------------------------------------
    @property
    def supports_verify(self):
        return True

    def verify(self, params, cache, pos, t, batch):
        """Verify a K+1 token window per row against the target model.

        cache: {"k", "v"} (L, B, C, KV, dh) ring buffers; pos: (B, C)
        per-row absolute slot positions (-1 empty); t: (B,) per-row next
        write position; batch: {"tokens": (B, K+1)} — the last sampled
        token followed by K draft proposals. Returns (greedy (B, K+1)
        int32, {"k", "v"}') where greedy[:, i] is the argmax
        continuation after feeding window token i. All K+1 slots
        t .. t+K are written optimistically (the caller must guarantee
        they carry pos == -1 on entry — the engine's no-wrap gate — and
        rolls back pos over the rejected suffix)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, K1 = tokens.shape
        C = cache["k"].shape[2]
        rows = jnp.arange(B)[:, None]                        # (B, 1)
        offs = t[:, None] + jnp.arange(K1)[None, :]          # (B, K1)
        slots = offs % C
        new_pos = pos.at[rows, slots].set(offs)
        x = self._embed(params, {"tokens": tokens})          # (B, K1, D)

        def body(x, inp):
            lp, ck, cv = inp

            def update(k1, v1):
                nk = ck.at[rows, slots].set(k1.astype(ck.dtype))
                nv = cv.at[rows, slots].set(v1.astype(cv.dtype))
                return nk, nv, new_pos

            x, (nk, nv) = _layer_verify(
                x, lp, {"update": update}, cfg, offs)
            return x, (nk, nv)

        x, (nks, nvs) = jax.lax.scan(body, x, (params["layers"],
                                               cache["k"], cache["v"]))
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = self._unembed(params, x)                    # (B, K1, V)
        gs = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return gs, {"k": nks, "v": nvs}

    def paged_verify(self, params, pool, table, pos, t, batch, *, page):
        """Paged-layout verify: gather each row's dense view through its
        page table, run the ring ``verify`` on it, scatter the K+1
        optimistically written slots back at per-row offsets. Same
        identity-by-construction argument as ``paged_decode``. pos:
        (B, C), t: (B,); returns (greedy, pool')."""
        cfg = self.cfg
        heads = (cfg.n_kv_heads, cfg.dh)
        layers = jnp.arange(cfg.n_layers)
        tokens = batch["tokens"]
        B, K1 = tokens.shape
        C = table.shape[1] * page
        gk, gv = paged_gather(pool["k"], pool["v"], table, heads,
                              layer=layers[:, None, None])
        greedy, nc = self.verify(params, {"k": gk, "v": gv}, pos, t,
                                 batch)
        slots = (t[:, None] + jnp.arange(K1)[None, :]) % C     # (B, K1)
        tbl_cols = jnp.take_along_axis(table, slots // page, axis=1)
        idx = jnp.broadcast_arrays(layers[:, None, None], tbl_cols[None],
                                   (slots % page)[None])
        idx = jnp.stack(idx, -1).reshape(-1, 3)    # (L * B * K1, 3)
        sel = slots[None, :, :, None, None]
        kw = jnp.take_along_axis(nc["k"], sel, axis=2)     # (L, B, K1, ...)
        vw = jnp.take_along_axis(nc["v"], sel, axis=2)
        nk, nv = paged_write_slots(pool["k"], pool["v"], idx,
                                   kw.reshape(-1, *heads),
                                   vw.reshape(-1, *heads))
        return greedy, {"k": nk, "v": nv}

    # ------------------------------------------------------------------
    # Paged KV cache protocol. The pool is layer-major page rows: each
    # of K and V is (L, P1, page * KV * dh), one physical page of one
    # layer per row (see attention.py). The forward math is *shared with
    # the ring path by construction*: paged_prefill runs the ordinary
    # prefill and only then scatters the dense cache into pool pages;
    # paged_decode's layer scan carries the pool, gathers each row's
    # pages of layer l into the dense (B, C, KV, dh) view the ring
    # decode's layer body expects, runs that body unchanged and writes
    # the page row holding the one new slot back into the carried pool,
    # in place. Logits therefore go through the same per-layer math on
    # the same values in both layouts — the token-identity the serving
    # equivalence tests assert is a property of the construction, not a
    # numerical accident.
    # ------------------------------------------------------------------
    @property
    def supports_paged_kv(self):
        # stub-embed (VLM) prefills prepend non-token positions, so the
        # prompt page <-> token page correspondence breaks
        return not self.cfg.n_stub_embeds

    def init_paged_pool(self, n_pages, page):
        # Pages as rows, layer-major: a layer's pages are one (P1, R)
        # plane that the decode scan gathers and writes in place, and
        # a page is one row of R = page * KV * dh elements. The row
        # count (n_pages + trash) is rounded up to a whole 8-row tile,
        # which the tiled TPU layout pads to anyway: then the device's
        # default layout keeps rows on the second-minor axis instead of
        # moving a short, pad-free axis (layers, experts) there, which
        # would put a transpose of the whole pool around every tick.
        cfg = self.cfg
        rows = -(-(n_pages + 1) // 8) * 8
        shape = (cfg.n_layers, rows, page * cfg.n_kv_heads * cfg.dh)
        cdt = dt(cfg.compute_dtype)
        return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}

    def paged_prefill(self, params, batch, pool, scatter_tbl, *, page,
                      capacity):
        """Ordinary prefill + page scatter. scatter_tbl: (B, S // page)
        physical destination pages (trash for rows whose compute is
        discarded). Returns (logits, pool', pos, t)."""
        logits, cache = self.prefill(params, batch, capacity=capacity)
        S = batch["tokens"].shape[1]
        k, v = cache["k"][:, :, :S], cache["v"][:, :, :S]
        nk, nv = paged_scatter_pages(pool["k"], pool["v"], scatter_tbl,
                                     k, v)
        return logits, {"k": nk, "v": nv}, cache["pos"], cache["t"]

    def paged_prefill_suffix(self, params, batch, pool, prefix_tbl,
                             scatter_tbl, *, offset, page):
        """Compute-shared suffix prefill: attend over cached prefix KV
        (gathered through ``prefix_tbl``, (B, offset // page)) and compute
        only the suffix tokens at absolute positions offset..offset+Ssuf-1.
        Suffix KV is scattered into pool pages via ``scatter_tbl``
        (B, Ssuf // page). Returns (logits, pool') where logits are the
        last suffix position's — causal masking makes them identical to a
        monolithic prefill of the full offset+Ssuf prompt."""
        cfg = self.cfg
        heads = (cfg.n_kv_heads, cfg.dh)
        x = self._embed(params, batch)
        Ssuf = x.shape[1]
        positions = jnp.arange(offset, offset + Ssuf)
        # gather the prefix view once per layer: (L, B, offset, KV, dh)
        gk, gv = paged_gather(pool["k"], pool["v"], prefix_tbl, heads,
                              layer=jnp.arange(cfg.n_layers)[:, None, None])

        def body(x, inp):
            lp, pk, pv = inp
            x, kv = _layer_suffix(x, lp, cfg, positions, pk, pv, offset)
            return x, kv

        if cfg.remat:
            body = jax.checkpoint(body)
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], gk, gv))
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = self._unembed(params, x[:, -1])
        nk, nv = paged_scatter_pages(pool["k"], pool["v"], scatter_tbl,
                                     ks, vs)
        return logits, {"k": nk, "v": nv}

    def paged_decode(self, params, pool, table, pos, t, batch, *, page):
        """One decode tick on the pool in place, layer by layer: the
        layer scan carries the pool, gathers only layer l's pages for
        each row, runs the ring decode's layer body on that view and
        writes the one new slot per row back into the carried pool.
        Nothing layer-stacked of cache size is built, and the pool is
        never relaid out. Returns (logits, pool', pos', t')."""
        cfg = self.cfg
        heads = (cfg.n_kv_heads, cfg.dh)
        x = self._embed(params, {"tokens": batch["token"]})
        B, n = table.shape
        slot = t % (n * page)
        tbl_col = jnp.take(table, slot // page, axis=1)
        kv_pos = jax.lax.dynamic_update_slice(pos, t[None], (slot,))
        # Selects, not dynamic_update_slice: under the bank's vmap each
        # expert has its own slot, which would turn an update into a
        # scatter that the TPU runs as a loop over experts (and over
        # rows, for a KV-wide window inside a page row). The selected
        # values are the same, so paged logits stay bitwise the ring's.
        at_slot = (jnp.arange(n * page) == slot)[:, None, None]
        W = cfg.n_kv_heads * cfg.dh
        in_row = jnp.arange(page * W) // W == slot % page

        def body(carry, inp):
            x, kp, vp = carry
            lp, l = inp
            ck, cv = paged_gather(kp, vp, table, heads, layer=l)
            new = {}                  # the layer body's k1, v1, kept here

            def update(k1, v1):
                new["k"], new["v"] = k1.astype(ck.dtype), v1.astype(cv.dtype)
                nk = jnp.where(at_slot, new["k"], ck)
                nv = jnp.where(at_slot, new["v"], cv)
                return nk, nv, kv_pos

            x, _ = _layer_decode(x, lp, {"update": update}, cfg, t)

            def write(pages, x1):
                # the slot's whole page row goes back, its other slots
                # unchanged: a written page belongs to one row (tail
                # pages are the row's own, wrapped shared pages are
                # copied first), padding rows all write the trash page
                row = jnp.where(in_row, jnp.tile(x1.reshape(B, -1), page),
                                pages[l, tbl_col])
                return pages.at[l, tbl_col].set(row)

            return (x, write(kp, new["k"]), write(vp, new["v"])), None

        (x, kp, vp), _ = jax.lax.scan(
            body, (x, pool["k"], pool["v"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = self._unembed(params, x[:, 0])
        return logits, {"k": kp, "v": vp}, kv_pos, t + 1

    # ------------------------------------------------------------------
    def input_shapes(self, sc):
        cfg = self.cfg
        if not cfg.n_stub_embeds:
            return super().input_shapes(sc)
        B, S = sc.global_batch, sc.seq_len
        f = jax.ShapeDtypeStruct
        i32, cdt = jnp.int32, dt(cfg.compute_dtype)
        stub = f((B, cfg.n_stub_embeds, cfg.d_model), cdt)
        n_txt = S - cfg.n_stub_embeds
        if sc.mode == "train":
            return {"tokens": f((B, n_txt), i32), "labels": f((B, n_txt), i32),
                    "stub_embeds": stub}
        if sc.mode == "prefill":
            return {"tokens": f((B, n_txt), i32), "stub_embeds": stub}
        return {"token": f((B, 1), i32)}
