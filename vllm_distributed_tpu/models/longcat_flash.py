"""LongCat-Flash decoder: latent (MLA) attention over a one-plane paged
cache, two attention sublayers and two dense FFNs a layer with the
routed layer on a shortcut, a router wider than the experts held.

One layer (``n`` are RMS norms with a weight, ``A`` an MLA sublayer,
``F`` a dense FFN, ``M`` the routed layer):

    h = x + A0(n0(x));  u = n1(h);  m = M(u);  h = h + F0(u)
    h = h + A1(n2(h));  y = h + F1(n3(h)) + m

**MLA.**  ``q = W_qb · norm(W_qa · x)``, per head a ``nope``
part and a ``rope`` part, both × (H / q_lora_rank)^½; ``[c, k_rope] =
W_kva · x``, ``c = norm(c)`` × (H / kv_lora_rank)^½, ``k_rope`` one
vector for all heads, unscaled; RoPE on ``q_rope`` and ``k_rope`` by
interleaved pairs.  What is cached is the row ``[c, k_rope]`` (one
plane, ``kv_lora_rank + qk_rope_head_dim`` values a token, key and value
at once), padded with zeros to whole 128-lane tiles: the chip lays a
576-lane row out over 640 lanes of HBM whatever the program calls it,
and its DMA engine takes whole tiles only, so the pool states the 640
it occupies.  A row that reads the cache attends in the absorbed form:
with ``W_kvb`` split per head into ``W_uk`` and ``W_uv``, ``q̃ = [q_nope
· W_uk, q_rope]``, scores ``q̃ · row`` × qk_head_dim^-½, ``o = (softmax
· c) · W_uv``.  A prompt chunk that starts at position 0 reads nothing
cached and attends per head over the step's own rows (``[k_nope, v] =
c · W_kvb``, the same two halves).  The model makes one call
(``ops/attention.mla_sublayer_attention``, head space in and out); which
rows take which form is decided there, from the step's metadata.  Cache
``2·layer + sublayer`` is sublayer
``sublayer`` of layer ``layer``.

**The router** is float32 throughout: ``s = softmax(W_r · u)`` over
``n_routed + zero_expert_num`` outputs (the first the routed experts,
the rest identity experts); the ``moe_topk`` indices are the top of
``s + b`` (``b`` = ``e_score_correction_bias``), the weights ``s`` at
those indices (no ``b``, not renormalised) × ``routed_scaling_factor``.

**This chip's share of the experts.**  ``config.json`` says how many
chips share a layer's experts (``expert_parallel_size``) and which of
them this is (``expert_parallel_rank``); ``n_routed_experts`` is what
this chip holds.  The router keeps every output of the deployment
(``n_routed_experts × expert_parallel_size + zero_expert_num``); a pick
of an expert held here adds that expert's product, a pick of an
identity expert adds ``u``, a pick of an expert held elsewhere adds
nothing: that partial ``m`` is what goes on.  Nothing stands in for the
absent chips or their exchange.

**Work follows the rows routed here.**  A step of T rows makes T·k
picks of which ``held / router width`` land here.  Each expert held
gathers its rows into a buffer of ``_capacity(T)`` rows (four times an
even router's mean) and the FFN runs over the buffers: in a step of
many rows (every expert is reached) as one batched product over the
held experts; in a step of few (a decode batch reaches about two
thirds of them) expert by expert, each behind a ``lax.cond`` that
skips an expert no row reached, so its weights are not read.  Should
any expert get more rows than its buffer holds, the layer takes its
other branch instead: every held expert over every row of the step,
weighted by the expert's own picks.  Both are exact; no pick is ever
dropped.  (``lax.cond`` is an HLO conditional, never a ``while``.)
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (
    CountsRouterPicks,
    SCOPE_ATTN_OUT,
    SCOPE_ATTN_QKV,
    SCOPE_EMBED,
    SCOPE_KV_WRITE,
    SCOPE_LM_HEAD,
    SCOPE_MLP,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTER,
    SupportsQuantization,
    apply_rope_interleaved,
    linear,
    rms_norm,
    rope_frequencies,
)
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    KVCacheLayout,
    mla_attention_reference,
    mla_sublayer_attention,
    write_kv_pages,
)
from vllm_distributed_tpu.utils import next_power_of_2

# Kinds of router pick, in the order of the counts ``forward`` reports.
PICK_KINDS = ("zero", "held", "absent")
_MIN_CAPACITY = 8
# Buffers of at least this many rows mean a step in which an even
# router reaches every held expert (P(none of T*k picks) under 2%):
# one batched product, no branch an expert.
_BATCHED_MIN_CAPACITY = 16
_LANES = 128


def _capacity(tokens: int, top_k: int, router_width: int) -> int:
    """Rows of one expert's gather buffer in a step of ``tokens`` rows:
    four times what an even router sends an expert, at least a sublane
    tile, never more than the step."""
    mean = math.ceil(tokens * top_k / router_width)
    return min(max(_MIN_CAPACITY, next_power_of_2(4 * mean)), tokens)


class LongcatFlashForCausalLM(CountsRouterPicks, SupportsQuantization):
    architectures = ("LongcatFlashForCausalLM",)
    # The large matmuls.  The router (float32 by the published code),
    # the per-head up-projections W_uk / W_uv (einsum operands of the
    # absorbed form), embedding and norms stay in the model dtype.
    QUANT_PARAMS = frozenset({
        "wq_a", "wq_b", "wkv_a", "wo", "gate", "up", "down",
        "w1", "w2", "w3", "lm_head",
    })
    # what ``forward`` counts on the device when given ``stats``; the
    # runner fetches the counts with the step's tokens
    moe_pick_kinds = PICK_KINDS

    def __init__(self, model_config: Any) -> None:
        hf = model_config.hf_config
        self.model_type = hf.model_type
        self.num_layers = model_config.get_num_layers()
        self.hidden_size = int(hf.hidden_size)
        self.num_heads = int(hf.num_attention_heads)
        self.q_lora_rank = int(hf.q_lora_rank)
        self.kv_lora_rank = int(hf.kv_lora_rank)
        self.nope_dim = int(hf.qk_nope_head_dim)
        self.rope_dim = int(hf.qk_rope_head_dim)
        self.v_dim = int(hf.v_head_dim)
        self.qk_dim = self.nope_dim + self.rope_dim
        self.latent_width = self.kv_lora_rank + self.rope_dim
        # a cached row as the pool holds it: whole lane tiles
        self.cache_width = -(-self.latent_width // _LANES) * _LANES
        self.ffn_size = int(hf.ffn_hidden_size)
        self.expert_ffn_size = int(hf.expert_ffn_hidden_size)
        self.vocab_size = int(hf.vocab_size)
        self.rope_theta = float(getattr(hf, "rope_theta", 1e7))
        if getattr(hf, "rope_scaling", None):
            raise NotImplementedError(
                "longcat_flash: rope_scaling is not supported (the "
                "published configuration has none)"
            )
        self.rms_eps = float(getattr(hf, "rms_norm_eps", 1e-5))
        # The two LoRA scales (true in the published configuration).
        self.scale_q = (
            (self.hidden_size / self.q_lora_rank) ** 0.5
            if getattr(hf, "mla_scale_q_lora", True) else 1.0
        )
        self.scale_kv = (
            (self.hidden_size / self.kv_lora_rank) ** 0.5
            if getattr(hf, "mla_scale_kv_lora", True) else 1.0
        )
        self.scale = self.qk_dim**-0.5
        # ---- the routed layer and this chip's share of it ----
        self.num_held = int(hf.n_routed_experts)
        self.ep_size = int(getattr(hf, "expert_parallel_size", 1))
        self.ep_rank = int(getattr(hf, "expert_parallel_rank", 0))
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"expert_parallel_rank {self.ep_rank} is not one of the "
                f"{self.ep_size} chips (expert_parallel_size) that share "
                "a layer's experts"
            )
        self.num_routed = self.num_held * self.ep_size
        self.num_zero = int(getattr(hf, "zero_expert_num", 0) or 0)
        self.router_width = self.num_routed + self.num_zero
        self.held_lo = self.ep_rank * self.num_held
        self.top_k = int(hf.moe_topk)
        self.routed_scale = float(getattr(hf, "routed_scaling_factor", 1.0))
        if getattr(hf, "router_bias", False):
            raise NotImplementedError("longcat_flash: router_bias")
        self.tie_embeddings = bool(getattr(hf, "tie_word_embeddings", False))
        if self.tie_embeddings:
            raise NotImplementedError("longcat_flash: tied embeddings")
        self.dtype = jnp.dtype(model_config.dtype)
        self._init_quant(model_config)

    # ---- what the runner reads of the caches ----
    def kv_cache_layout(self) -> KVCacheLayout:
        """Two caches a layer, each one plane of latent rows; no heads,
        so no int8 scale plane and nothing to shard over tp."""
        return KVCacheLayout(
            num_caches=2 * self.num_layers,
            planes=1,
            width=self.cache_width,
            scale_width=0,
        )

    def kv_cache_spec(self) -> P:
        return P(None, None, None, None)

    def validate_mesh(self, mesh) -> None:
        tp = mesh.shape.get("tp", 1)
        if tp > 1:
            raise ValueError(
                f"longcat_flash cannot run on a mesh with tp={tp}: a "
                "latent cache row has no heads to shard and the chip's "
                "share of the experts comes from config.json "
                "(expert_parallel_size/rank), not from a mesh "
                "(models/longcat_flash.py)"
            )

    # ---- params ----
    # Per layer, in this order: sublayers 0 and 1 (wq_a, wq_b, wkv_a,
    # wkv_b, wo), dense FFNs 0 and 1 (gate, up, down), router, router
    # bias, w1, w3, w2: 21 keys; then the embedding and the head.
    KEYS_PER_LAYER = 21

    def init_params(self, rng: jax.Array) -> dict:
        """Seeded weights (tests, --load-format dummy): one root key
        split into ``21 · layers + 2``; every matrix ``normal × 0.02``
        rounded to the model dtype, norms ones, the router's correction
        bias ``normal × (1 / router width)`` in float32 (of the order of a
        score: it moves some picks, not all).  The benchmark's
        reference repeats this (benchmark/reference/longcat_flash.py)."""
        h, nh = self.hidden_size, self.num_heads
        keys = jax.random.split(rng, self.KEYS_PER_LAYER * self.num_layers + 2)

        def nrm(key, shape):
            return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
                self.dtype
            )

        def ones(n):
            return jnp.ones((n,), self.dtype)

        layers = []
        for i in range(self.num_layers):
            lk = keys[self.KEYS_PER_LAYER * i:]
            attn = []
            for j in range(2):
                k = lk[5 * j:]
                up = self.nope_dim + self.v_dim
                wkv_b = nrm(k[3], (self.kv_lora_rank, nh * up)).reshape(
                    self.kv_lora_rank, nh, up
                )
                attn.append({
                    "ln": ones(h),
                    "wq_a": nrm(k[0], (h, self.q_lora_rank)),
                    "q_ln": ones(self.q_lora_rank),
                    "wq_b": nrm(k[1], (self.q_lora_rank, nh * self.qk_dim)),
                    "wkv_a": nrm(k[2], (h, self.latent_width)),
                    "kv_ln": ones(self.kv_lora_rank),
                    # W_kvb per head: q_nope · W_uk [nope, latent],
                    # attended latent · W_uv [latent, v]
                    "w_uk": wkv_b[:, :, : self.nope_dim].transpose(1, 2, 0),
                    "w_uv": wkv_b[:, :, self.nope_dim:].transpose(1, 0, 2),
                    "wo": nrm(k[4], (nh * self.v_dim, h)),
                })
            ffn = []
            for j in range(2):
                k = lk[10 + 3 * j:]
                ffn.append({
                    "ln": ones(h),
                    "gate": nrm(k[0], (h, self.ffn_size)),
                    "up": nrm(k[1], (h, self.ffn_size)),
                    "down": nrm(k[2], (self.ffn_size, h)),
                })
            e, im = self.num_held, self.expert_ffn_size
            layers.append({
                "attn": attn,
                "ffn": ffn,
                "router": nrm(lk[16], (h, self.router_width)),
                "router_bias": jax.random.normal(
                    lk[17], (self.router_width,), jnp.float32
                ) * (1.0 / self.router_width),
                "w1": nrm(lk[18], (e, h, im)),
                "w3": nrm(lk[19], (e, h, im)),
                "w2": nrm(lk[20], (e, im, h)),
            })
        n = self.KEYS_PER_LAYER * self.num_layers
        return {
            "embed": nrm(keys[n], (self.vocab_size, h)),
            "layers": layers,
            "norm": ones(h),
            "lm_head": nrm(keys[n + 1], (h, self.vocab_size)),
        }

    def partition_specs(self) -> dict:
        """Everything replicated: tp > 1 is refused (validate_mesh)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def map_hf_name(self, name: str):
        """HF safetensors name -> (param path, 'T' to transpose).  The
        fused ``kv_b_proj`` and the experts this chip does not hold have
        no home in this tree: a published checkpoint cannot be loaded
        yet (ROADMAP R-M2), only seeded weights."""
        raise NotImplementedError(
            "longcat_flash loads seeded weights only (--load-format "
            "dummy): splitting kv_b_proj per head and taking this chip's "
            "experts out of a checkpoint is not written"
        )

    def fuse_quantized_projections(self, params: dict) -> dict:
        """Nothing to fuse: no two projections of a sublayer read the
        same input at the same width class (q and kv go down to
        different ranks, with a norm between the two halves of each)."""
        return params

    # ---- forward ----
    def _attention(self, x, a: dict, kv, meta, inv_freq, attn_fn, kv_write_fn):
        t = x.shape[0]
        nh = self.num_heads
        with jax.named_scope(SCOPE_ATTN_QKV):
            h = rms_norm(x, a["ln"], self.rms_eps)
            qa = rms_norm(linear(h, a["wq_a"]), a["q_ln"], self.rms_eps)
            q = linear(qa, a["wq_b"]).reshape(t, nh, self.qk_dim)
            q = (q.astype(jnp.float32) * self.scale_q).astype(x.dtype)
            q_nope, q_rope = q[..., : self.nope_dim], q[..., self.nope_dim:]
            q_rope = apply_rope_interleaved(q_rope, meta.q_positions, inv_freq)
            ckr = linear(h, a["wkv_a"])  # [T, latent + rope]
            c = rms_norm(ckr[:, : self.kv_lora_rank], a["kv_ln"], self.rms_eps)
            c = (c.astype(jnp.float32) * self.scale_kv).astype(x.dtype)
            k_rope = apply_rope_interleaved(
                ckr[:, None, self.kv_lora_rank:], meta.q_positions, inv_freq
            )
            pad = jnp.zeros(
                (t, 1, self.cache_width - self.latent_width), x.dtype
            )
            row = jnp.concatenate([c[:, None, :], k_rope, pad], axis=-1)
        with jax.named_scope(SCOPE_KV_WRITE):
            kv = kv_write_fn(kv, row, None, meta.slot_mapping)
        o = mla_sublayer_attention(
            attn_fn, q_nope, q_rope, c, k_rope[:, 0], a["w_uk"], a["w_uv"],
            kv, meta, scale=self.scale,
        )
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + linear(o.reshape(t, nh * self.v_dim), a["wo"])
        return x, kv

    def _ffn(self, h, f: dict):
        return linear(
            jax.nn.silu(linear(h, f["gate"])) * linear(h, f["up"]), f["down"]
        )

    def _route(self, u, layer: dict):
        """Router, float32 throughout: (indices [T, k], weights [T, k])."""
        logits = jnp.dot(
            u.astype(jnp.float32),
            layer["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        scores = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(scores + layer["router_bias"], self.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1) * self.routed_scale
        return idx.astype(jnp.int32), w

    def _experts(self, xs, layer: dict, e: int | None = None, row_w=None):
        """The held experts' FFN in float32: expert ``e`` over rows
        [n, H]; every expert over its own rows [held, n, H]; or, with
        ``row_w`` [n, held], every expert over the same rows [n, H],
        weighted and summed over the experts."""
        from vllm_distributed_tpu.ops.quant import maybe_dequantize

        w1, w3, w2 = (
            maybe_dequantize(layer[n], xs.dtype) for n in ("w1", "w3", "w2")
        )
        if e is not None:
            inner = jax.nn.silu(xs @ w1[e]) * (xs @ w3[e])
            return (inner @ w2[e]).astype(jnp.float32)
        rows = "nh" if row_w is not None else "enh"
        inner = jax.nn.silu(
            jnp.einsum(f"{rows},ehi->eni", xs, w1)
        ) * jnp.einsum(f"{rows},ehi->eni", xs, w3)
        if row_w is None:
            return jnp.einsum(
                "eni,eih->enh", inner, w2, preferred_element_type=jnp.float32
            )
        inner = (inner.astype(jnp.float32) * row_w.T[:, :, None]).astype(
            xs.dtype
        )
        return jnp.einsum(
            "eni,eih->nh", inner, w2, preferred_element_type=jnp.float32
        )

    def _moe(self, u, layer: dict, live):
        """The routed layer's part of this chip: (m [T, H] in u.dtype,
        picks [3] int32 by PICK_KINDS over the live rows)."""
        t, h = u.shape
        k, held = self.top_k, self.num_held
        with jax.named_scope(SCOPE_MOE_ROUTER):
            idx, w = self._route(u, layer)
            w = jnp.where(live[:, None], w, 0.0)
            is_zero = idx >= self.num_routed
            local = idx - self.held_lo
            is_held = (local >= 0) & (local < held) & ~is_zero
            picks = jnp.stack([
                (is_zero & live[:, None]).sum(dtype=jnp.int32),
                (is_held & live[:, None]).sum(dtype=jnp.int32),
                (~is_zero & ~is_held & live[:, None]).sum(dtype=jnp.int32),
            ])
            w_zero = jnp.where(is_zero, w, 0.0).sum(axis=-1)  # [T]
            # Each held pick's place in its expert's buffer: its rank
            # among the picks of that expert, in token order.
            cap = _capacity(t, k, self.router_width)
            flat_e = jnp.where(is_held & live[:, None], local, held)
            flat_e = flat_e.reshape(-1)  # [T*k]; ``held`` = none here
            onehot = flat_e[:, None] == jnp.arange(held, dtype=jnp.int32)
            rank = jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1
            counts = onehot.sum(axis=0, dtype=jnp.int32)  # [held]
            rank = jnp.where(onehot, rank, cap).min(axis=1)  # [T*k]
            slot = jnp.where(
                rank < cap, flat_e * cap + rank, held * cap
            )  # out of range: dropped by the scatters below
            tok = jnp.arange(t * k, dtype=jnp.int32) // k
            slot_tok = jnp.zeros(held * cap, jnp.int32).at[slot].set(
                tok, mode="drop"
            ).reshape(held, cap)
            slot_w = jnp.zeros(held * cap, jnp.float32).at[slot].set(
                w.reshape(-1), mode="drop"
            ).reshape(held, cap)
            # An expert over its buffer weighs every row by its picks.
            row_w = jnp.where(
                onehot.reshape(t, k, held), w[..., None], 0.0
            ).sum(axis=1)  # [T, held]
            over = (counts > cap).any()

        with jax.named_scope(SCOPE_MOE_EXPERTS):
            y = w_zero[:, None] * u.astype(jnp.float32)

            def gathered_all(y):
                out = self._experts(u[slot_tok], layer) * slot_w[..., None]
                return y.at[slot_tok.reshape(-1)].add(out.reshape(-1, h))

            def gathered_each(y):
                for e in range(held):
                    def reached(y, e=e):
                        out = self._experts(u[slot_tok[e]], layer, e)
                        return y.at[slot_tok[e]].add(out * slot_w[e][:, None])

                    y = jax.lax.cond(counts[e] > 0, reached, lambda y: y, y)
                return y

            def every_row(y):
                return y + self._experts(u, layer, row_w=row_w)

            gathered = (
                gathered_all if cap >= _BATCHED_MIN_CAPACITY else gathered_each
            )
            if cap < t:  # a buffer of the whole step cannot be over
                y = jax.lax.cond(over, every_row, gathered, y)
            else:
                y = gathered(y)
        return y.astype(u.dtype), picks

    def forward(
        self,
        params: dict,
        token_ids: jax.Array,  # [T]
        kv_caches: list,  # 2 a layer: latent pools [1, P, page, cache_width]
        meta: AttentionMetadata,
        attn_fn: Callable = mla_attention_reference,
        kv_write_fn: Callable = write_kv_pages,
        return_hidden: bool = False,
        stats: dict | None = None,
    ) -> tuple:
        """Returns (logits [S, V] at meta.logits_indices, updated
        caches); with ``stats`` a dict, also leaves the router's pick
        counts there (``stats["moe_picks"]``: [3] int32 by PICK_KINDS,
        summed over the layers, padding rows not counted)."""
        with jax.named_scope(SCOPE_EMBED):
            x = params["embed"][token_ids].astype(self.dtype)
        inv_freq = rope_frequencies(self.rope_dim, self.rope_theta)
        live = meta.q_seq_ids < meta.seq_lens.shape[0]
        new_kv = []
        picks = jnp.zeros(3, jnp.int32)
        for i, layer in enumerate(params["layers"]):
            x, kv = self._attention(
                x, layer["attn"][0], kv_caches[2 * i], meta, inv_freq,
                attn_fn, kv_write_fn,
            )
            new_kv.append(kv)
            with jax.named_scope(SCOPE_MLP):
                u = rms_norm(x, layer["ffn"][0]["ln"], self.rms_eps)
            m, layer_picks = self._moe(u, layer, live)
            picks = picks + layer_picks
            with jax.named_scope(SCOPE_MLP):
                x = x + self._ffn(u, layer["ffn"][0])
            x, kv = self._attention(
                x, layer["attn"][1], kv_caches[2 * i + 1], meta, inv_freq,
                attn_fn, kv_write_fn,
            )
            new_kv.append(kv)
            with jax.named_scope(SCOPE_MLP):
                h = rms_norm(x, layer["ffn"][1]["ln"], self.rms_eps)
                x = x + self._ffn(h, layer["ffn"][1]) + m
        if stats is not None:
            stats["moe_picks"] = picks

        with jax.named_scope(SCOPE_LM_HEAD):
            x = rms_norm(x, params["norm"], self.rms_eps)
            sel = x[meta.logits_indices]  # [S, H]
            logits = linear(sel, params["lm_head"])
        logits = logits.astype(jnp.float32)
        if return_hidden:
            return logits, new_kv, sel.astype(jnp.float32)
        return logits, new_kv
