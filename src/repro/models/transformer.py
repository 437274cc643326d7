"""Unified decoder-only LM covering all assigned architecture families.

One parametric model implements: dense GQA decoders (qwen/granite/phi4/
danube/llava backbone/musicgen), MoE decoders (dbrx/olmoe), the hymba
hybrid block (parallel attention + selective-SSM heads), and RWKV-6.
Multimodal frontends (ViT patches / EnCodec frames) are stubs: the model
consumes precomputed frame/patch embeddings alongside token embeddings.

Layers are stacked along a leading L axis and applied with `lax.scan`
(+ optional `jax.checkpoint`), which keeps HLO size and 512-device compile
times tractable for 88-layer configs.

Three entry points (built into jitted steps by ``repro.launch.steps``):
  * ``forward``      -- train/eval logits over a full sequence
  * ``prefill``      -- forward + populated KV/state caches
  * ``decode_step``  -- one token against a (circular) cache
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import ssm as S
from repro.models.config import ArchConfig
from repro.models.sharding import NO_SHARDING, ShardingRules
from jax.sharding import PartitionSpec as P


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


class LM:
    def __init__(self, cfg: ArchConfig, rules: ShardingRules = NO_SHARDING,
                 remat: bool = True, q_chunk: int = 1024,
                 kv_chunk: int = 1024, dtype=jnp.bfloat16,
                 layer_loop: str = "scan"):
        assert cfg.tp >= 1 and cfg.head_dim, "config must be resolve()d"
        assert layer_loop in ("scan", "unrolled")
        self.cfg = cfg
        self.rules = rules
        self.remat = remat
        self.q_chunk = q_chunk
        self.kv_chunk = kv_chunk
        self.dtype = dtype
        # "unrolled" replaces the layer scan with a python loop: used by the
        # dry-run's metric compiles (cost_analysis counts a scan body once,
        # so roofline terms are extrapolated from unrolled 1/2-layer builds)
        self.layer_loop = layer_loop
        self.seq_parallel = True
        # one-hot matmul embedding: needed for sharded TRAINING gradients
        # (steps.lower_prefill/lower_decode switch it off -- see _embed)
        self.embed_onehot = True
        if cfg.n_heads:
            # map (padded) q head -> true kv head; padded heads reuse head 0
            g = max(1, cfg.n_heads // cfg.n_kv_heads)
            self.kv_map = np.array(
                [min(i // g, cfg.n_kv_heads - 1) if i < cfg.n_heads else 0
                 for i in range(cfg.n_heads_padded)])
            self.grouped = cfg.n_heads_padded % cfg.n_kv_heads == 0
        else:
            self.kv_map, self.grouped = None, False

    # ---- parameters ----------------------------------------------------------

    def _init_layer(self, key):
        cfg, dt = self.cfg, self.dtype
        ks = iter(jax.random.split(key, 24))
        p = {}
        if cfg.block == "rwkv":
            p["ln1"] = jnp.ones((cfg.d_model,), dt)
            p["ln2"] = jnp.ones((cfg.d_model,), dt)
            p["rwkv"] = S.rwkv_init(next(ks), cfg.d_model, cfg.d_ff, dt)
            return p
        hd, Hq, Hkv = cfg.head_dim, cfg.n_heads_padded, cfg.n_kv_heads
        p["ln1"] = jnp.ones((cfg.d_model,), dt)
        p["ln2"] = jnp.ones((cfg.d_model,), dt)
        p["wq"] = _init(next(ks), (cfg.d_model, Hq * hd), 0.02, dt)
        p["wk"] = _init(next(ks), (cfg.d_model, Hkv * hd), 0.02, dt)
        p["wv"] = _init(next(ks), (cfg.d_model, Hkv * hd), 0.02, dt)
        p["wo"] = _init(next(ks), (Hq * hd, cfg.d_model), 0.02, dt)
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((Hq * hd,), dt)
            p["bk"] = jnp.zeros((Hkv * hd,), dt)
            p["bv"] = jnp.zeros((Hkv * hd,), dt)
        if cfg.block == "hybrid":
            p["ssm"] = S.ssm_init(next(ks), cfg.d_model, cfg.ssm.state_dim,
                                  cfg.ssm.expand, cfg.ssm.conv_width, dt)
        if cfg.moe:
            E = cfg.moe.n_experts
            p["moe"] = {
                "router": _init(next(ks), (cfg.d_model, E), 0.02, jnp.float32),
                "wg": _init(next(ks), (E, cfg.d_model, cfg.d_ff), 0.02, dt),
                "wu": _init(next(ks), (E, cfg.d_model, cfg.d_ff), 0.02, dt),
                "wo": _init(next(ks), (E, cfg.d_ff, cfg.d_model), 0.02, dt),
            }
        else:
            p["mlp"] = {"wu": _init(next(ks), (cfg.d_model, cfg.d_ff), 0.02, dt),
                        "wo": _init(next(ks), (cfg.d_ff, cfg.d_model), 0.02, dt)}
            if cfg.act == "swiglu":
                p["mlp"]["wg"] = _init(next(ks), (cfg.d_model, cfg.d_ff), 0.02, dt)
        return p

    def init_params(self, key):
        cfg, dt = self.cfg, self.dtype
        k_emb, k_head, k_layers = jax.random.split(key, 3)
        params = {
            "embed": _init(k_emb, (cfg.vocab_padded, cfg.d_model), 0.02, dt),
            "final_norm": jnp.ones((cfg.d_model,), dt),
            "layers": jax.vmap(self._init_layer)(
                jax.random.split(k_layers, cfg.n_layers)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = _init(k_head, (cfg.d_model, cfg.vocab_padded),
                                      0.02, dt)
        return params

    def abstract_params(self):
        return jax.eval_shape(self.init_params, jax.random.PRNGKey(0))

    # ---- parameter partition specs --------------------------------------------

    def param_specs(self, fsdp: bool | None = None):
        """Parameter PartitionSpecs.  With ``fsdp`` (default: on when
        sharding is enabled), each weight's d_model-like dim is additionally
        sharded over the data axis (ZeRO-3): GSPMD all-gathers weights
        just-in-time per layer and reduce-scatters their grads, removing
        the data-axis replication of params + optimizer state."""
        cfg = self.cfg
        m = self.rules.model_axis          # None = pure-FSDP (no TP)
        fsdp = self.rules.enabled if fsdp is None else fsdp
        d = self.rules.fsdp_dim if fsdp else None
        kv_shardable = cfg.n_kv_heads and cfg.n_kv_heads % cfg.tp == 0
        kv = P(None, d, m) if kv_shardable else P(None, d, None)
        kvb = P(None, m) if kv_shardable else P(None, None)
        lay = {}
        if cfg.block == "rwkv":
            lay = {"ln1": P(None, None), "ln2": P(None, None),
                   "rwkv": {
                       "att": {"mu": P(None, None, None),
                               "wr": P(None, d, m), "wk": P(None, d, m),
                               "wv": P(None, d, m), "wg": P(None, d, m),
                               "ww": P(None, d, m),
                               "w_bias": P(None, None),
                               "u": P(None, m, None),
                               "wo": P(None, m, d)},
                       "ffn": {"mu": P(None, None, None),
                               "wk": P(None, d, m),
                               "wv": P(None, m, d),
                               "wr": P(None, d, None)}}}
        else:
            lay = {"ln1": P(None, None), "ln2": P(None, None),
                   "wq": P(None, d, m), "wk": kv, "wv": kv,
                   "wo": P(None, m, d)}
            if cfg.qkv_bias:
                lay.update({"bq": P(None, m), "bk": kvb, "bv": kvb})
            if cfg.block == "hybrid":
                lay["ssm"] = {"in_proj": P(None, d, m),
                              "conv": P(None, None, m),
                              "wdt": P(None, m),
                              "wB": P(None, m, None), "wC": P(None, m, None),
                              "logA": P(None, m, None),
                              "out_proj": P(None, m, d),
                              "dskip": P(None, m)}
            if cfg.moe:
                lay["moe"] = {"router": P(None, None, None),
                              "wg": P(None, m, d, None),
                              "wu": P(None, m, d, None),
                              "wo": P(None, m, None, d)}
            else:
                mlp = {"wu": P(None, d, m), "wo": P(None, m, d)}
                if cfg.act == "swiglu":
                    mlp["wg"] = P(None, d, m)
                lay["mlp"] = mlp
        specs = {"embed": P(m, d), "final_norm": P(None), "layers": lay}
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(d, m)
        return specs

    # ---- sublayers -------------------------------------------------------------

    def _expand_all_kv(self, k):
        """Expand kv heads to the full (padded) q head count via take."""
        if k.shape[2] == self.cfg.n_heads_padded:
            return k
        return jnp.take(k, jnp.asarray(self.kv_map), axis=2)

    def _attn(self, lp, h, positions, cache=None, pos=None):
        cfg, rules = self.cfg, self.rules
        B, Sq, D = h.shape
        hd, Hq, Hkv = cfg.head_dim, cfg.n_heads_padded, cfg.n_kv_heads
        q = jnp.einsum("bsd,de->bse", h, lp["wq"])
        k = jnp.einsum("bsd,de->bse", h, lp["wk"])
        v = jnp.einsum("bsd,de->bse", h, lp["wv"])
        if cfg.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(B, Sq, Hq, hd)
        k = k.reshape(B, Sq, Hkv, hd)
        v = v.reshape(B, Sq, Hkv, hd)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        q = rules.constrain(q, "batch", None, "model", None)

        def pin(t):
            return self.rules.constrain(t, None, "batch", None,
                                        "model", None)

        new_cache = None
        if cache is None:                               # train/eval, no cache
            ke, ve = self._expand_all_kv(k), self._expand_all_kv(v)
            out = L.flash_attention(q, ke, ve, causal=True,
                                    window=cfg.sliding_window,
                                    q_chunk=self.q_chunk,
                                    kv_chunk=self.kv_chunk, constrain=pin)
        elif Sq > 1:                                    # prefill into cache
            T = cache["k"].shape[1]
            kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, 0, 0, 0))
            new_cache = {"k": kc, "v": vc}
            ke, ve = self._expand_all_kv(k), self._expand_all_kv(v)
            out = L.flash_attention(q, ke, ve, causal=True,
                                    window=cfg.sliding_window,
                                    q_chunk=self.q_chunk,
                                    kv_chunk=self.kv_chunk, constrain=pin)
        else:                                           # single-token decode
            T = cache["k"].shape[1]
            idx = pos % T                               # circular buffer
            kc = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
            new_cache = {"k": kc, "v": vc}
            # circular buffer: once pos >= T every slot holds one of the
            # last T tokens (T = sliding window for SWA archs)
            n_valid = jnp.minimum(pos + 1, T)
            valid = (jnp.arange(T) < n_valid)[None, :].repeat(B, 0)
            if self.grouped:        # grouped decode: no kv expansion,
                ke, ve = kc, vc     # cache stays at true kv heads
            else:
                ke, ve = self._expand_all_kv(kc), self._expand_all_kv(vc)
            out = L.decode_attention(q, ke, ve, valid)
        out = out.reshape(B, Sq, Hq * hd)
        return jnp.einsum("bse,ed->bsd", out, lp["wo"]), new_cache

    def _ffn(self, lp, h):
        cfg = self.cfg
        if cfg.moe:
            y, aux = L.moe_apply(
                lp["moe"], h, n_experts=cfg.moe.n_experts,
                top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor, act=cfg.act,
                constrain=(self.rules.constrain if self.rules.enabled
                           else None),
                seq_chunks=(self.cfg.tp if h.shape[1] % self.cfg.tp == 0
                            else 1))
            return y, aux
        return L.mlp_apply(lp["mlp"], h, cfg.act), 0.0

    def _layer(self, lp, x, positions, cache=None, pos=None):
        """One block. Returns (x, new_cache_layer, aux)."""
        cfg = self.cfg
        if cfg.block == "rwkv":
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            sx0 = cache["sx_att"] if cache else jnp.zeros(
                (x.shape[0], cfg.d_model), x.dtype)
            st0 = cache["wkv"] if cache else jnp.zeros(
                (x.shape[0], cfg.d_model // S.RWKV_HEAD_DIM,
                 S.RWKV_HEAD_DIM, S.RWKV_HEAD_DIM), jnp.float32)
            y, sx_att, wkv = S.rwkv_time_mix(lp["rwkv"]["att"], h, sx0, st0)
            x = x + y
            h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            sx1 = cache["sx_ffn"] if cache else jnp.zeros(
                (x.shape[0], cfg.d_model), x.dtype)
            y, sx_ffn = S.rwkv_channel_mix(lp["rwkv"]["ffn"], h, sx1)
            x = x + y
            new_cache = {"wkv": wkv, "sx_att": sx_att.astype(x.dtype),
                         "sx_ffn": sx_ffn.astype(x.dtype)} if cache else None
            return x, new_cache, 0.0

        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        attn_out, attn_cache = self._attn(
            lp, h, positions,
            cache=({"k": cache["k"], "v": cache["v"]} if cache else None),
            pos=pos)
        mix = attn_out
        new_cache = dict(attn_cache) if attn_cache else None
        if cfg.block == "hybrid":
            st = (cache["ssm_state"], cache["conv"]) if cache else (None, None)
            ssm_out, (ssm_state, conv) = S.ssm_apply(lp["ssm"], h,
                                                     state=st[0],
                                                     conv_carry=st[1])
            mix = mix + ssm_out
            if cache:
                new_cache.update({"ssm_state": ssm_state, "conv": conv})
        # constrain the (partial-sum) sublayer output to the stream spec
        # BEFORE the residual add: GSPMD then emits a reduce-scatter into
        # the sequence-sharded domain instead of a full all-reduce (2x wire)
        x = x + self._constrain_stream(mix)
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, aux = self._ffn(lp, h)
        x = self._constrain_stream(x + self._constrain_stream(y))
        return x, new_cache, aux

    def _constrain_stream(self, x):
        """Residual stream: sequence-parallel over the model axis when the
        sequence divides (Megatron-SP); the per-layer gather/scatter GSPMD
        inserts costs the same wire bytes as the plain all-reduce but cuts
        the remat-saved activations by the TP degree."""
        if x.shape[1] > 1 and x.shape[1] % self.cfg.tp == 0 and self.seq_parallel:
            return self.rules.constrain(x, "batch", "model", None)
        return self.rules.constrain(x, "batch", None, None)

    # ---- embeddings / logits ----------------------------------------------------

    def _embed(self, params, tokens, embeds):
        xs = []
        if embeds is not None:
            xs.append(embeds.astype(self.dtype))
        if tokens is not None:
            if self.rules.enabled and self.embed_onehot:
                # one-hot matmul (training): the take()-gather's scatter-add
                # backward replicates the full-vocab f32 gradient on every
                # device; the matmul form keeps fwd and bwd vocab-sharded.
                # At inference (no gradient) the plain gather is far
                # cheaper: the one-hot itself is (B, S, V) -- 7.8 GB/dev
                # for llava's 32k prefill.
                oh = jax.nn.one_hot(tokens, params["embed"].shape[0],
                                    dtype=self.dtype)
                xs.append(jnp.einsum("bsv,vd->bsd", oh, params["embed"]))
            else:
                xs.append(jnp.take(params["embed"], tokens, axis=0))
        x = jnp.concatenate(xs, axis=1) if len(xs) > 1 else xs[0]
        return self._constrain_stream(x)

    def _head(self, params, x):
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", x, head)
        return self.rules.constrain(logits, "batch", None, "model")

    def _logits(self, params, x):
        x = L.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        return self._head(params, x)

    # ---- entry points -------------------------------------------------------------

    def _backbone(self, params, tokens=None, embeds=None):
        """Embed + layer stack + final norm. Returns (x (B,S,D), aux)."""
        x = self._embed(params, tokens, embeds)
        positions = jnp.arange(x.shape[1])[None, :]

        def body(xc, lp):
            # the barrier stops XLA from hoisting the rms_norm bf16->f32
            # convert of the whole saved activation stack out of the
            # backward loop (a 2x-per-elem temp blowup otherwise)
            xc = jax.lax.optimization_barrier(xc)
            xo, _, aux = self._layer(lp, xc, positions)
            return xo, aux

        if self.remat:
            body = jax.checkpoint(body)
        if self.layer_loop == "unrolled":
            auxs = []
            for i in range(self.cfg.n_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                x, aux = body(x, lp)
                auxs.append(aux)
            aux = jnp.mean(jnp.stack(auxs))
        else:
            x, auxs = jax.lax.scan(body, x, params["layers"])
            aux = jnp.mean(auxs)
        x = L.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        return x, aux

    def forward(self, params, tokens=None, embeds=None):
        """Train/eval forward. Returns (logits (B,S,Vp), moe aux loss)."""
        x, aux = self._backbone(params, tokens, embeds)
        return self._head(params, x), aux

    def forward_loss(self, params, tokens, labels, loss_mask=None,
                     embeds=None, loss_chunk: int = 512):
        """Fused chunked cross-entropy: never materializes (B,S,Vp) logits.

        The head matmul + CE run per sequence chunk under jax.checkpoint,
        so peak logits memory is (B, chunk, Vp/tp) and the backward
        recomputes each chunk's logits instead of saving them.
        """
        x, aux = self._backbone(params, tokens, embeds)
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        B, S, D = x.shape
        c = min(loss_chunk, S)
        n = S // c
        assert S % c == 0
        xs = jnp.moveaxis(x.reshape(B, n, c, D), 1, 0)
        ls = jnp.moveaxis(labels.reshape(B, n, c), 1, 0)
        if loss_mask is None:
            loss_mask = jnp.ones((B, S), jnp.float32)
        ms = jnp.moveaxis(loss_mask.reshape(B, n, c), 1, 0)

        @jax.checkpoint
        def body(carry, inp):
            xc, lc, mc = inp
            logits = jnp.einsum("bsd,dv->bsv", xc, head)
            logits = self.rules.constrain(logits, "batch", None, "model")
            nll, msum = _chunk_ce(logits, lc, mc, self.cfg.vocab)
            return (carry[0] + nll, carry[1] + msum), None

        (nll, msum), _ = jax.lax.scan(body, (0.0, 0.0), (xs, ls, ms))
        return nll / jnp.maximum(msum, 1.0), aux

    def init_cache(self, batch: int, capacity: int):
        cfg, dt = self.cfg, self.dtype
        c = {}
        if cfg.block in ("attn", "hybrid"):
            kv_shape = (cfg.n_layers, batch, capacity, cfg.n_kv_heads,
                        cfg.head_dim)
            c["k"] = jnp.zeros(kv_shape, dt)
            c["v"] = jnp.zeros(kv_shape, dt)
        if cfg.block == "hybrid":
            di = cfg.ssm.expand * cfg.d_model
            c["ssm_state"] = jnp.zeros(
                (cfg.n_layers, batch, di, cfg.ssm.state_dim), jnp.float32)
            c["conv"] = jnp.zeros(
                (cfg.n_layers, batch, cfg.ssm.conv_width - 1, di), dt)
        if cfg.block == "rwkv":
            H = cfg.d_model // S.RWKV_HEAD_DIM
            c["wkv"] = jnp.zeros((cfg.n_layers, batch, H, S.RWKV_HEAD_DIM,
                                  S.RWKV_HEAD_DIM), jnp.float32)
            c["sx_att"] = jnp.zeros((cfg.n_layers, batch, cfg.d_model), dt)
            c["sx_ffn"] = jnp.zeros((cfg.n_layers, batch, cfg.d_model), dt)
        return {"layers": c, "pos": jnp.zeros((), jnp.int32)}

    def cache_specs(self, rules: ShardingRules | None = None):
        """PartitionSpecs for the cache pytree."""
        r = rules or self.rules
        cfg = self.cfg
        kv_shardable = cfg.n_kv_heads and cfg.n_kv_heads % cfg.tp == 0
        # kv cache: batch over data; heads over model when divisible,
        # otherwise sequence over model (sequence-parallel decode attention).
        kv = (r.spec(None, "batch", None, "model", None) if kv_shardable
              else r.spec(None, "batch", "model", None, None))
        c = {}
        if cfg.block in ("attn", "hybrid"):
            c["k"] = kv
            c["v"] = kv
        if cfg.block == "hybrid":
            c["ssm_state"] = r.spec(None, "batch", "model", None)
            c["conv"] = r.spec(None, "batch", None, "model")
        if cfg.block == "rwkv":
            c["wkv"] = r.spec(None, "batch", "model", None, None)
            c["sx_att"] = r.spec(None, "batch", None)
            c["sx_ffn"] = r.spec(None, "batch", None)
        return {"layers": c, "pos": P()}

    def prefill(self, params, tokens=None, embeds=None, capacity=None):
        """Forward pass that also populates caches. Returns (logits, cache)."""
        x = self._embed(params, tokens, embeds)
        B, Sq = x.shape[0], x.shape[1]
        capacity = capacity or Sq
        cache0 = self.init_cache(B, capacity)
        positions = jnp.arange(Sq)[None, :]

        def body(xc, inp):
            lp, cl = inp
            xo, new_cl, aux = self._layer(lp, xc, positions, cache=cl,
                                          pos=jnp.zeros((), jnp.int32))
            return xo, (new_cl, aux)

        if self.remat:
            body = jax.checkpoint(body)
        if self.layer_loop == "unrolled":
            outs = []
            for i in range(self.cfg.n_layers):
                inp = jax.tree.map(lambda a: a[i],
                                   (params["layers"], cache0["layers"]))
                x, out = body(x, inp)
                outs.append(out)
            new_layers = jax.tree.map(lambda *a: jnp.stack(a), *
                                      [o[0] for o in outs])
        else:
            x, (new_layers, _) = jax.lax.scan(
                body, x, (params["layers"], cache0["layers"]))
        cache = {"layers": new_layers,
                 "pos": jnp.full((), Sq, jnp.int32)}
        return self._logits(params, x[:, -1:]), cache

    def decode_step(self, params, cache, tokens):
        """One decode step. tokens: (B, 1). Returns (logits (B,1,Vp), cache)."""
        x = self._embed(params, tokens, None)
        pos = cache["pos"]
        positions = jnp.full((x.shape[0], 1), pos)

        if self.layer_loop == "unrolled":
            outs = []
            for i in range(self.cfg.n_layers):
                lp, cl = jax.tree.map(lambda a: a[i],
                                      (params["layers"], cache["layers"]))
                x, out, _ = self._layer(lp, x, positions, cache=cl, pos=pos)
                outs.append(out)
            new_layers = jax.tree.map(lambda *a: jnp.stack(a), *outs)
            cache = {"layers": new_layers, "pos": pos + 1}
            return self._logits(params, x), cache

        # cache travels as scan CARRY with per-layer dynamic updates: with
        # donation the update aliases in place.  (As xs/ys the stacked cache
        # is copied input->output through the loop: 2x cache temp.)
        def body(carry, inp):
            xc, cl_all = carry
            i, lp = inp
            cl = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                cl_all)
            xo, new_cl, _ = self._layer(lp, xc, positions, cache=cl, pos=pos)
            cl_all = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
                cl_all, new_cl)
            return (xo, cl_all), None

        (x, new_layers), _ = jax.lax.scan(
            body, (x, cache["layers"]),
            (jnp.arange(self.cfg.n_layers), params["layers"]))
        cache = {"layers": new_layers, "pos": pos + 1}
        return self._logits(params, x), cache


# ---- loss ----------------------------------------------------------------------

def _chunk_ce(logits, labels, mask, vocab: int | None):
    """Summed masked CE over one chunk. Returns (sum_nll, sum_mask)."""
    logits = logits.astype(jnp.float32)
    if vocab is not None and vocab < logits.shape[-1]:
        live = jnp.arange(logits.shape[-1]) < vocab
        logits = jnp.where(live, logits, -1e9)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return nll.sum(), mask.sum()


def lm_loss(logits, labels, mask=None, vocab: int | None = None):
    """Mean next-token cross-entropy. logits: (B,S,Vp), labels: (B,S)."""
    logits = logits.astype(jnp.float32)
    if vocab is not None and vocab < logits.shape[-1]:
        live = jnp.arange(logits.shape[-1]) < vocab
        logits = jnp.where(live, logits, -1e9)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
    return jnp.mean(nll)
