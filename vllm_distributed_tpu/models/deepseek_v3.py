"""DeepSeek-V3-architecture decoder (``DeepseekV3ForCausalLM``; the
benchmark runs GigaChat3.1-702B-A36B's published sizes): a plain
pre-norm block with one latent (MLA) attention sublayer a layer, YaRN
rotary frequencies, values wider or narrower than keys, leading dense
layers and then a routed layer with a group-limited sigmoid router and
an always-on shared expert.

One layer ``l`` (``n`` RMS norms with a weight)::

    h = x + A(n(x));   y = h + F_l(n(h))

``F_l`` is a dense SwiGLU FFN for ``l < first_k_dense_replace``, else
``F(u) = S(u) + R(u)``: ``S`` the shared expert (a SwiGLU of width
``n_shared_experts · moe_intermediate_size``), ``R`` the routed part.

**The router** is float32 throughout: ``s = sigmoid(W_r · u)`` over
every routed expert of the deployment; ``s' = s + b`` (``b`` =
``e_score_correction_bias``: it moves the choice and never a weight);
the experts lie in ``n_group`` groups of consecutive experts, a group's
score is the sum of its two largest ``s'``, the ``topk_group`` best
groups stay and every other expert's ``s'`` is masked to 0; the indices
are the top ``num_experts_per_tok`` of the masked ``s'``; the weights
``s`` at those indices, divided by their sum + 1e-20 when
``norm_topk_prob``, times ``routed_scaling_factor``.

**This chip's share of the experts.**  As in models/longcat_flash.py,
``config.json`` says how many chips share a layer's experts
(``expert_parallel_size``) and which of them this is
(``expert_parallel_rank``); ``n_routed_experts`` is what this chip
holds.  The router keeps every output, every group and the
normalisation over all picks; a pick of an expert held here adds that
expert's product, a pick held elsewhere adds nothing, the shared expert
is added whole: that partial result goes on.  Nothing stands in for the
absent chips or their exchange.  The experts run through the routed
layer every model shares (models/routed_experts.py), which is handed
indices local to the held stack.

**MLA.**  ``q = W_qb · n(W_qa · x)``, per head ``[q_nope | q_rope]``;
``[c | k_rope] = W_kva · x``, ``c = n(c)``, ``k_rope`` one vector for
all heads; RoPE on ``q_rope`` and ``k_rope`` with YaRN's frequencies
(models/common.yarn_inv_freq; the cos/sin factor
``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``);
the softmax scale is ``(nope + rope)^-½ · yarn_mscale(factor,
mscale_all_dim)²``.  The cached row is ``[c | k_rope]`` padded to whole
128-lane tiles, one cache a layer; rows that read the cache attend in
the absorbed form with ``W_kvb`` split per head into ``W_uk [nope,
latent]`` and ``W_uv [latent, v]``, a chunk from position 0 per head
(``ops/attention.mla_sublayer_attention`` decides); ``v_head_dim`` need
not equal ``qk_nope_head_dim``.

**One departure from the published code**: RoPE rotates interleaved
pairs (lanes 2i, 2i+1; ``apply_rope_interleaved``) where the published
code first permutes each pair's lanes to (i, i + d/2) and rotates
halves.  The permutation is the same on ``q_rope`` and ``k_rope``, so
every score is the same; the reference does likewise.

Not supported, and refused at load by name: a mesh with tp > 1
(``validate_mesh``), speculative decoding (``refuses_spec_decode``:
the published multi-token-prediction module is not loaded), and what
any latent cache refuses (worker/model_runner.py).
"""

from __future__ import annotations

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
    SCOPE_MOE_ROUTER,
    SCOPE_MOE_SHARED,
    SupportsQuantization,
    apply_rope_interleaved,
    linear,
    rms_norm,
    rope_frequencies,
    yarn_inv_freq,
    yarn_mscale,
)
from vllm_distributed_tpu.models.routed_experts import (
    RoutedLayerCounts,
    local_expert_ids,
    picks_by_expert,
    routed_experts,
    select_moe_impl,
)
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    KVCacheLayout,
    mla_attention_reference,
    mla_sublayer_attention,
    write_kv_pages,
)

# What ``forward`` counts of the router's picks on the device, over the
# live rows and all routed layers: picks of experts held here, picks of
# experts held elsewhere (the two add up to all picks), and each
# layer's busiest held expert's picks summed (the rows a grouped kernel
# pads the other groups towards).  ``skipped`` is no pick: the held
# experts no live row reached in a layer of the ``branch`` form, whose
# conditional did not run and whose matrices were not read (0 in a
# program of another form).
PICK_KINDS = ("held", "absent", "busiest", "skipped")
# The seeded router bias, in float32.  A sigmoid of logits of spread
# ~1.7 (hidden 7168 at initializer_range 0.02) puts the top picks'
# scores about 0.01 apart: a bias of that order moves about one pick in
# six and leaves the rest (0.1 would move two in three, and swing the
# held share of a seed between 2% and 9%).
ROUTER_BIAS_STD = 0.01
_LANES = 128


class DeepseekV3ForCausalLM(
    RoutedLayerCounts, CountsRouterPicks, SupportsQuantization
):
    architectures = ("DeepseekV3ForCausalLM",)
    # The large matmuls.  The router (float32 by the published code),
    # the per-head up-projections W_uk / W_uv (einsum operands of the
    # absorbed form), embedding and norms stay in the model dtype.
    QUANT_PARAMS = frozenset({
        "wq_a", "wq_b", "wkv_a", "wo", "gate", "up", "down",
        "w1", "w2", "w3", "lm_head",
    })
    moe_pick_kinds = PICK_KINDS
    # One single-step program a (token, longest-chunk) bucket whatever
    # sequences the step holds (models/lfm2_moe.py has the finding).
    pin_step_seq_bucket = True
    # why the runner refuses --speculative-ngram-k at load
    refuses_spec_decode = (
        "what a speculative configuration of this model means is its "
        "published multi-token-prediction module, which is not loaded "
        "(ROADMAP.md R-M6)"
    )
    # Per layer: wq_a, wq_b, wkv_a, wkv_b, wo; then the feed-forward's
    # eight (dense: gate, up, down, five unused; routed: the shared
    # expert's gate, up, down, router, router bias, w1, w3, w2).
    KEYS_PER_LAYER = 13

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
        self.intermediate_size = int(hf.intermediate_size)
        self.moe_intermediate = int(hf.moe_intermediate_size)
        self.shared_size = self.moe_intermediate * int(
            getattr(hf, "n_shared_experts", 0) or 0
        )
        self.num_dense = min(
            int(getattr(hf, "first_k_dense_replace", 0)), self.num_layers
        )
        if int(getattr(hf, "moe_layer_freq", 1)) != 1:
            raise NotImplementedError("deepseek_v3: moe_layer_freq != 1")
        self.vocab_size = int(hf.vocab_size)
        self.rms_eps = float(getattr(hf, "rms_norm_eps", 1e-6))
        self.init_std = float(getattr(hf, "initializer_range", 0.02))
        # ---- rotary frequencies and the softmax scale ----
        self.rope_theta = float(getattr(hf, "rope_theta", 10000.0))
        scaling = getattr(hf, "rope_scaling", None)
        self.rope_scaling = dict(scaling) if scaling else None
        self.scale = self.qk_dim**-0.5
        self.rope_factor = 1.0  # on cos and sin
        if self.rope_scaling is not None:
            kind = self.rope_scaling.get(
                "rope_type", self.rope_scaling.get("type")
            )
            if kind != "yarn":
                raise NotImplementedError(
                    f"deepseek_v3: rope_scaling of type {kind!r} (the "
                    "published configurations use yarn)"
                )
            factor = float(self.rope_scaling["factor"])
            all_dim = yarn_mscale(
                factor, float(self.rope_scaling.get("mscale_all_dim", 0))
            )
            self.scale *= all_dim * all_dim
            self.rope_factor = yarn_mscale(
                factor, float(self.rope_scaling.get("mscale", 1))
            ) / all_dim
        # ---- the router and this chip's share of the experts ----
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
        self.held_lo = self.ep_rank * self.num_held
        self.top_k = int(hf.num_experts_per_tok)
        self.n_group = int(getattr(hf, "n_group", 1) or 1)
        self.topk_group = int(getattr(hf, "topk_group", 1) or 1)
        if self.num_routed % self.n_group or not (
            0 < self.topk_group <= self.n_group
        ):
            raise ValueError(
                f"deepseek_v3: {self.num_routed} routed experts "
                f"(n_routed_experts x expert_parallel_size) in n_group "
                f"{self.n_group} groups, topk_group {self.topk_group}"
            )
        if self.num_routed // self.n_group < 2:
            raise ValueError(
                "deepseek_v3: a group's score is the sum of its two "
                "largest, so a group holds at least two experts"
            )
        if getattr(hf, "scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(
                f"deepseek_v3: scoring_func {hf.scoring_func!r}"
            )
        if getattr(hf, "topk_method", "noaux_tc") != "noaux_tc":
            raise NotImplementedError(
                f"deepseek_v3: topk_method {hf.topk_method!r}"
            )
        self.norm_topk = bool(getattr(hf, "norm_topk_prob", True))
        self.routed_scale = float(getattr(hf, "routed_scaling_factor", 1.0))
        if getattr(hf, "attention_bias", False):
            raise NotImplementedError("deepseek_v3: attention_bias")
        if getattr(hf, "tie_word_embeddings", False):
            raise NotImplementedError("deepseek_v3: tied embeddings")
        self.dtype = jnp.dtype(model_config.dtype)
        # ``step_counts`` names a program's routed form from this layer
        self.first_routed_layer = min(self.num_dense, self.num_layers - 1)
        # The backend the unquantized experts' many-row form may use:
        # the grouped kernel where Pallas runs, ragged_dot elsewhere.
        from vllm_distributed_tpu import envs
        from vllm_distributed_tpu.ops import resolve_kernel_backend

        self.kernel_backend = resolve_kernel_backend(envs.VDT_USE_PALLAS)
        self._init_quant(model_config)

    # ---- what the runner reads of the caches ----
    def kv_cache_layout(self) -> KVCacheLayout:
        """One cache a layer, one plane of latent rows; no heads, so no
        int8 scale plane and nothing to shard over tp."""
        return KVCacheLayout(
            num_caches=self.num_layers,
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
                f"deepseek_v3 cannot run on a mesh with tp={tp}: a "
                "latent cache row has no heads to shard and the chip's "
                "share of the experts comes from config.json "
                "(expert_parallel_size/rank), not from a mesh "
                "(models/deepseek_v3.py)"
            )

    # ---- params ----
    def init_params(self, rng: jax.Array) -> dict:
        """Seeded weights (tests, --load-format dummy): one root key
        split into ``13 · layers + 2``; layer ``i`` takes keys ``13i ..``
        in KEYS_PER_LAYER's order, then the embedding and the head.
        Every matrix ``normal × initializer_range`` rounded to the model
        dtype, norms ones, the router's correction bias ``normal ×
        0.01`` in float32.  The benchmark's reference repeats this
        (benchmark/reference/deepseek_v3.py)."""
        h, nh = self.hidden_size, self.num_heads
        keys = jax.random.split(rng, self.KEYS_PER_LAYER * self.num_layers + 2)

        def nrm(key, shape):
            return (
                jax.random.normal(key, shape, jnp.float32) * self.init_std
            ).astype(self.dtype)

        def ones(n):
            return jnp.ones((n,), self.dtype)

        def ffn(k, width):
            return {
                "gate": nrm(k[0], (h, width)),
                "up": nrm(k[1], (h, width)),
                "down": nrm(k[2], (width, h)),
            }

        layers = []
        for i in range(self.num_layers):
            k = keys[self.KEYS_PER_LAYER * i:]
            up = self.nope_dim + self.v_dim
            wkv_b = nrm(k[3], (self.kv_lora_rank, nh * up)).reshape(
                self.kv_lora_rank, nh, up
            )
            layer = {
                "attn": {
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
                },
                "ffn_ln": ones(h),
            }
            if i < self.num_dense:
                layer["ffn"] = ffn(k[5:], self.intermediate_size)
            else:
                e, im = self.num_held, self.moe_intermediate
                if self.shared_size:
                    layer["shared"] = ffn(k[5:], self.shared_size)
                layer.update(
                    router=nrm(k[8], (h, self.num_routed)),
                    router_bias=jax.random.normal(
                        k[9], (self.num_routed,), jnp.float32
                    ) * ROUTER_BIAS_STD,
                    w1=nrm(k[10], (e, h, im)),
                    w3=nrm(k[11], (e, h, im)),
                    w2=nrm(k[12], (e, im, h)),
                )
            layers.append(layer)
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
        raise NotImplementedError(
            "deepseek_v3 loads seeded weights only (--load-format "
            "dummy): splitting kv_b_proj per head and taking this chip's "
            "experts out of a checkpoint is not written"
        )

    def fuse_quantized_projections(self, params: dict) -> dict:
        """Nothing to fuse (q and kv go down to different ranks with a
        norm between the halves of each); every model has the method:
        tests/benchmark/test_bm_compile_rehearsal.py calls it."""
        return params

    # ---- forward ----
    def _inv_freq(self) -> jax.Array:
        if self.rope_scaling is None:
            return rope_frequencies(self.rope_dim, self.rope_theta)
        return yarn_inv_freq(self.rope_dim, self.rope_theta, self.rope_scaling)

    def _rope(self, x, positions, inv_freq):
        x = apply_rope_interleaved(x, positions, inv_freq)
        if self.rope_factor != 1.0:
            x = (x.astype(jnp.float32) * self.rope_factor).astype(x.dtype)
        return x

    def _attention(self, x, a: dict, kv, meta, inv_freq, attn_fn, kv_write_fn):
        t = x.shape[0]
        nh = self.num_heads
        with jax.named_scope(SCOPE_ATTN_QKV):
            h = rms_norm(x, a["ln"], self.rms_eps)
            qa = rms_norm(linear(h, a["wq_a"]), a["q_ln"], self.rms_eps)
            q = linear(qa, a["wq_b"]).reshape(t, nh, self.qk_dim)
            q_nope, q_rope = q[..., : self.nope_dim], q[..., self.nope_dim:]
            q_rope = self._rope(q_rope, meta.q_positions, inv_freq)
            ckr = linear(h, a["wkv_a"])  # [T, latent + rope]
            c = rms_norm(ckr[:, : self.kv_lora_rank], a["kv_ln"], self.rms_eps)
            k_rope = self._rope(
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

    def _ffn(self, u, f: dict):
        return linear(
            jax.nn.silu(linear(u, f["gate"])) * linear(u, f["up"]), f["down"]
        )

    def _scores(self, u, layer: dict):
        """The router's sigmoid scores [T, routed], float32 throughout."""
        logits = jnp.dot(
            u.astype(jnp.float32),
            layer["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return jax.nn.sigmoid(logits)

    def _route(self, u, layer: dict):
        """Router, float32 throughout: (indices [T, k] over every routed
        expert of the deployment, weights [T, k])."""
        t = u.shape[0]
        scores = self._scores(u, layer)
        chosen_by = scores + layer["router_bias"]
        groups = chosen_by.reshape(t, self.n_group, -1)
        group_score = jax.lax.top_k(groups, 2)[0].sum(axis=-1)  # [T, G]
        _, kept = jax.lax.top_k(group_score, self.topk_group)
        in_kept = (
            kept[:, :, None] == jnp.arange(self.n_group, dtype=kept.dtype)
        ).any(axis=1)  # [T, G]
        masked = jnp.where(in_kept[:, :, None], groups, 0.0).reshape(t, -1)
        _, idx = jax.lax.top_k(masked, self.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if self.norm_topk:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * self.routed_scale

    def moe_impl(self, layer: dict, tokens: int) -> str:
        """What the routed layers of a step of ``tokens`` rows are
        traced with: "dense" | "ragged" | "grouped" | "branch", by the
        rows an even router sends the experts held *here* (``tokens · k
        · held / routed``), not by all the step's picks; and, where that
        says the dense einsum, by whether so few rows are expected to
        leave a held expert unreached (``branch``)."""
        from vllm_distributed_tpu import envs

        rows_here = -(-tokens * self.top_k * self.num_held // self.num_routed)
        return select_moe_impl(
            envs.VDT_MOE_IMPL, rows_here, layer["w1"],
            on_mesh=False, unquantized_backend=self.kernel_backend,
            tokens=tokens, top_k=self.top_k, router_width=self.num_routed,
        )

    def _moe(self, u, layer: dict, live):
        """This chip's part of the routed experts' sum: (R(u) [T, H] in
        u.dtype, picks [4] int32 by PICK_KINDS over the live rows)."""
        held = self.num_held
        impl = self.moe_impl(layer, u.shape[0])
        with jax.named_scope(SCOPE_MOE_ROUTER):
            idx, w = self._route(u, layer)
            local = local_expert_ids(idx, self.held_lo, held)
            _, counts = picks_by_expert(local, held, live)  # [held]
            n_held = counts.sum()
            n_all = live.sum(dtype=jnp.int32) * self.top_k
            # the branch form's own condition: an expert with no pick
            skipped = (
                (counts == 0).sum(dtype=jnp.int32) if impl == "branch"
                else jnp.zeros((), jnp.int32)
            )
            picks = jnp.stack(
                [n_held, n_all - n_held, counts.max(), skipped]
            )
        out = routed_experts(
            impl, u, local, w,
            layer["w1"], layer["w3"], layer["w2"], live=live,
            interpret=self.kernel_backend == "pallas_interpret",
        )
        return out, picks

    def forward(
        self,
        params: dict,
        token_ids: jax.Array,  # [T]
        kv_caches: list,  # one a layer: latent pools [1, P, page, cache_width]
        meta: AttentionMetadata,
        attn_fn: Callable = mla_attention_reference,
        kv_write_fn: Callable = write_kv_pages,
        return_hidden: bool = False,
        stats: dict | None = None,
    ) -> tuple:
        """Returns (logits [S, V] at meta.logits_indices, updated
        caches); with ``stats`` a dict, also leaves the router's pick
        counts there (``stats["moe_picks"]``: [4] int32 by PICK_KINDS,
        summed over the routed layers, padding rows not counted)."""
        with jax.named_scope(SCOPE_EMBED):
            x = params["embed"][token_ids].astype(self.dtype)
        inv_freq = self._inv_freq()
        live = meta.q_seq_ids < meta.seq_lens.shape[0]
        new_kv = []
        picks = jnp.zeros(len(PICK_KINDS), jnp.int32)
        for i, layer in enumerate(params["layers"]):
            x, kv = self._attention(
                x, layer["attn"], kv_caches[i], meta, inv_freq, attn_fn,
                kv_write_fn,
            )
            new_kv.append(kv)
            with jax.named_scope(SCOPE_MLP):
                u = rms_norm(x, layer["ffn_ln"], self.rms_eps)
                if i < self.num_dense:
                    x = x + self._ffn(u, layer["ffn"])
            if i >= self.num_dense:
                m, layer_picks = self._moe(u, layer, live)
                picks = picks + layer_picks
                if self.shared_size:
                    with jax.named_scope(SCOPE_MOE_SHARED):
                        m = m + self._ffn(u, layer["shared"])
                with jax.named_scope(SCOPE_MLP):
                    x = x + m
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
