"""LFM2-MoE decoder (LiquidAI LFM2-8B-A1B): gated short-convolution
layers that keep two rows of state a *sequence* where an attention
layer keeps a key and a value a *token*, grouped-query attention with
per-head q/k norms in the few layers that have it, leading dense SwiGLU
layers and then a sigmoid router whose bias moves the choice and never
a weight.

One layer ``l`` (all norms RMS with a weight)::

    h = x + Op_l(n_op(x));   y = h + FF_l(n_ff(h))

``Op_l`` by ``layer_types[l]``:

- ``conv`` — the gated short convolution.  ``[B, C, X] = W_in · u``
  (three hidden-wide parts, in that order); ``z = B ⊙ X``; ``c_t = w_0
  ⊙ z_{t-2} + w_1 ⊙ z_{t-1} + w_2 ⊙ z_t`` with ``z_{<0} = 0`` (a
  depthwise causal convolution of kernel ``conv_L_cache`` = 3, one
  weight triple a channel, no bias, no activation); ``out = W_out · (C
  ⊙ c)``.  **The state** after token ``t`` is ``(z_{t-1}, z_t)``: two
  rows a sequence whatever its length, stated by ``seq_state_layout()``
  beside ``kv_cache_layout()`` and held by the runner in a row of the
  sequence's own (``ops/attention.SeqState``).  A step computes a flat
  batch of ragged chunks: a token's two predecessors come from the
  batch where they lie in the same chunk, from the state row where the
  chunk starts mid-sequence, and are zero before position 0, so a row
  that changed hands needs no clearing.  Plain ``jax.numpy``: shifted
  multiply-adds and selects, no loop.
- ``full_attention`` — ``q, k, v`` projections, RMS norm over each
  head's lanes of ``q`` and of ``k``, RoPE by half-rotation, causal
  softmax over the paged cache (``num_key_value_heads`` KV heads), the
  output projection.  Only these layers hold a paged cache: attention
  layer ``j`` (in layer order) reads and writes cache ``j``.

``FF_l`` is a dense SwiGLU FFN for ``l < num_dense_layers`` and the
routed layer after.  **The router** is float32 throughout: ``s =
sigmoid(W_g · x)`` over ``num_experts`` outputs; the
``num_experts_per_tok`` indices are the top of ``s + b`` (``b`` =
``expert_bias``), the weights ``s`` at those indices, divided by their
sum + 1e-6 when ``norm_topk_prob``, times ``routed_scaling_factor``.
The experts run through the routed layer every model shares
(models/routed_experts.py).  No shared expert.  Then the final norm and
a head tied to the embedding.

Not supported, and refused at load by name: a mesh (``validate_mesh``),
and with any per-sequence state: prefix caching, speculative decoding,
the host spill tier, KV transfer (worker/model_runner.py).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.config import LAYER_TYPES
from vllm_distributed_tpu.models.common import (
    CountsRouterPicks,
    SCOPE_ATTN,
    SCOPE_ATTN_OUT,
    SCOPE_ATTN_QKV,
    SCOPE_EMBED,
    SCOPE_KV_WRITE,
    SCOPE_LM_HEAD,
    SCOPE_MLP,
    SCOPE_MOE_ROUTER,
    SCOPE_SHORT_CONV,
    SupportsQuantization,
    apply_rope,
    linear,
    rms_norm,
    rope_frequencies,
)
from vllm_distributed_tpu.models.routed_experts import (
    RoutedLayerCounts,
    routed_experts,
    select_moe_impl,
)
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    KVCacheLayout,
    SeqState,
    SeqStateLayout,
    paged_attention_reference,
    write_kv_pages,
)

# What ``forward`` counts of the router's picks on the device, over the
# live rows and all routed layers: every pick, and each layer's busiest
# expert's picks summed (the rows a grouped kernel pads the others to).
PICK_KINDS = ("all", "busiest")
# The seeded draws that are not ``normal × initializer_range``: the
# router's bias is of the order of the scores' spread (a sigmoid of
# logits of spread ~0.9 spreads by ~0.2), so that it moves some picks
# and not all; a convolution's three taps are of the order of 3^-½,
# as a fan-in of three would initialise them.
EXPERT_BIAS_STD = 0.1
CONV_TAP_STD = 3.0**-0.5


class Lfm2MoeForCausalLM(
    RoutedLayerCounts, CountsRouterPicks, SupportsQuantization
):
    architectures = ("Lfm2MoeForCausalLM",)
    # The large matmuls.  The router (float32), its bias, the taps, the
    # embedding (gathered, and tied to the head) and the norms stay in
    # the model dtype.
    QUANT_PARAMS = frozenset({
        "wq", "wk", "wv", "wo", "in_proj", "out_proj",
        "gate", "up", "down", "w1", "w2", "w3",
    })
    moe_pick_kinds = PICK_KINDS
    # the worker counter the picks go to (``vllm:moe_expert_picks_total``)
    moe_pick_counter = "moe_expert_picks"
    KEYS_PER_LAYER = 9
    # Single steps are compiled at the runner's sequence bucket whatever
    # sequences they hold (the state arrays have that many rows anyway,
    # and the attention kernel's tile lists skip absent sequences): a
    # step of few sequences — the first after an idle stretch, a request
    # served alone — is no program of its own.  With growth bucketing a
    # rarely met (tokens, longest chunk, sequences) shape was first
    # compiled inside one benchmark window in four (PERF.md, PR 38).
    pin_step_seq_bucket = True

    def __init__(self, model_config: Any) -> None:
        hf = model_config.hf_config
        self.model_type = hf.model_type
        self.num_layers = model_config.get_num_layers()
        self.hidden_size = int(hf.hidden_size)
        self.num_heads = int(hf.num_attention_heads)
        self.num_kv_heads = int(hf.num_key_value_heads)
        # the published file has no head_dim: hidden / heads
        self.head_dim = model_config.get_head_dim()
        self.layer_types = tuple(hf.layer_types)
        assert set(self.layer_types) <= set(LAYER_TYPES)  # config.py checks
        self.num_dense = int(getattr(hf, "num_dense_layers", 0))
        self.conv_taps = int(hf.conv_L_cache)
        if self.conv_taps != 3:
            raise NotImplementedError(
                f"lfm2_moe: conv_L_cache {self.conv_taps}; the short "
                "convolution is written for the published kernel of 3"
            )
        if getattr(hf, "conv_bias", False):
            raise NotImplementedError("lfm2_moe: conv_bias")
        self.intermediate_size = int(hf.intermediate_size)
        self.moe_intermediate = int(hf.moe_intermediate_size)
        self.num_experts = int(hf.num_experts)
        self.top_k = int(hf.num_experts_per_tok)
        self.norm_topk = bool(getattr(hf, "norm_topk_prob", True))
        self.use_expert_bias = bool(getattr(hf, "use_expert_bias", True))
        self.routed_scale = float(getattr(hf, "routed_scaling_factor", 1.0))
        self.vocab_size = int(hf.vocab_size)
        self.rope_theta = float(getattr(hf, "rope_theta", 1e6))
        if getattr(hf, "rope_scaling", None):
            raise NotImplementedError("lfm2_moe: rope_scaling")
        self.rms_eps = float(getattr(hf, "norm_eps", 1e-5))
        self.init_std = float(getattr(hf, "initializer_range", 0.02))
        if not getattr(hf, "tie_word_embeddings", True):
            raise NotImplementedError(
                "lfm2_moe: an output head of its own (the published "
                "model ties it to the embedding)"
            )
        self.dtype = jnp.dtype(model_config.dtype)
        self.scale = self.head_dim**-0.5
        self.attn_layers = tuple(
            i for i, kind in enumerate(self.layer_types)
            if kind == "full_attention"
        )
        self.conv_layers = tuple(
            i for i, kind in enumerate(self.layer_types) if kind == "conv"
        )
        # ``step_counts`` names a program's routed form from this layer
        self.first_routed_layer = min(self.num_dense, self.num_layers - 1)
        # The backend the unquantized experts' many-row form may use:
        # the grouped kernel where Pallas runs, ragged_dot elsewhere.
        from vllm_distributed_tpu import envs
        from vllm_distributed_tpu.ops import resolve_kernel_backend

        self.kernel_backend = resolve_kernel_backend(envs.VDT_USE_PALLAS)
        self._init_quant(model_config)

    # ---- what the runner reads of the caches and the state ----
    def kv_cache_layout(self) -> KVCacheLayout:
        """One paged cache an *attention* layer, not a layer."""
        return KVCacheLayout.by_heads(
            len(self.attn_layers), self.num_kv_heads, self.head_dim
        )

    def seq_state_layout(self) -> SeqStateLayout:
        """One state a convolution layer: the last ``conv_L_cache - 1``
        gated rows of a sequence."""
        return SeqStateLayout.one_kind(
            "conv", len(self.conv_layers), self.conv_taps - 1,
            self.hidden_size,
        )

    def kv_cache_spec(self) -> P:
        return P(None, None, None, None)

    def validate_mesh(self, mesh) -> None:
        if mesh.devices.size > 1:
            raise ValueError(
                f"lfm2_moe cannot run on a mesh of {mesh.devices.size} "
                "devices: its per-sequence convolution state and its "
                "expert stack have no partition rule yet "
                "(models/lfm2_moe.py)"
            )

    # ---- params ----
    def init_params(self, rng: jax.Array) -> dict:
        """Seeded weights (tests, --load-format dummy): one root key
        split into ``9 · layers + 1``.  Layer ``i`` takes keys ``9i ..``:
        the operator's four (convolution: in_proj, taps, out_proj, one
        unused; attention: wq, wk, wv, wo), then the feed-forward's five
        (dense: gate, up, down, two unused; routed: router, bias, w1,
        w3, w2); the last key is the embedding's.  Every matrix ``normal
        × initializer_range`` rounded to the model dtype, norms ones,
        the taps ``normal × 3^-½``, the router's bias ``normal × 0.1`` in
        float32.  The benchmark's reference repeats this
        (benchmark/reference/lfm2_moe.py)."""
        h, nh, nkv, d = (
            self.hidden_size, self.num_heads, self.num_kv_heads,
            self.head_dim,
        )
        keys = jax.random.split(rng, self.KEYS_PER_LAYER * self.num_layers + 1)

        def nrm(key, shape, std=self.init_std):
            return (jax.random.normal(key, shape, jnp.float32) * std).astype(
                self.dtype
            )

        def ones(n):
            return jnp.ones((n,), self.dtype)

        layers = []
        for i, kind in enumerate(self.layer_types):
            k = keys[self.KEYS_PER_LAYER * i:]
            layer = {"op_ln": ones(h), "ffn_ln": ones(h)}
            if kind == "conv":
                layer.update(
                    in_proj=nrm(k[0], (h, 3 * h)),
                    conv_w=nrm(k[1], (self.conv_taps, h), CONV_TAP_STD),
                    out_proj=nrm(k[2], (h, h)),
                )
            else:
                layer.update(
                    wq=nrm(k[0], (h, nh * d)),
                    wk=nrm(k[1], (h, nkv * d)),
                    wv=nrm(k[2], (h, nkv * d)),
                    wo=nrm(k[3], (nh * d, h)),
                    q_norm=ones(d),
                    k_norm=ones(d),
                )
            if i < self.num_dense:
                im = self.intermediate_size
                layer.update(
                    gate=nrm(k[4], (h, im)),
                    up=nrm(k[5], (h, im)),
                    down=nrm(k[6], (im, h)),
                )
            else:
                e, im = self.num_experts, self.moe_intermediate
                layer.update(
                    router=nrm(k[4], (h, e)),
                    expert_bias=jax.random.normal(k[5], (e,), jnp.float32)
                    * EXPERT_BIAS_STD,
                    w1=nrm(k[6], (e, h, im)),
                    w3=nrm(k[7], (e, h, im)),
                    w2=nrm(k[8], (e, im, h)),
                )
            layers.append(layer)
        return {
            "embed": nrm(keys[-1], (self.vocab_size, h)),
            "layers": layers,
            "norm": ones(h),
        }

    def partition_specs(self) -> dict:
        """Everything replicated: a mesh is refused (validate_mesh)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def map_hf_name(self, name: str):
        raise NotImplementedError(
            "lfm2_moe loads seeded weights only (--load-format dummy): "
            "reshaping the published conv.conv.weight [H, 1, 3] into "
            "taps and stacking the per-expert tensors is not written"
        )

    def fuse_quantized_projections(self, params: dict) -> dict:
        return params

    # ---- forward ----
    def _short_conv(self, x, layer: dict, state, seq: SeqState, meta):
        """One gated short-convolution operator over the flat batch:
        (x + out [T, H], the layer's state with this step's sequences'
        rows rewritten)."""
        t, w = x.shape
        with jax.named_scope(SCOPE_SHORT_CONV):
            u = rms_norm(x, layer["op_ln"], self.rms_eps)
            bcx = linear(u, layer["in_proj"])  # [T, 3H]
            b, c, xg = bcx[:, :w], bcx[:, w : 2 * w], bcx[:, 2 * w :]
            z = b * xg  # [T, H]: what the state keeps
            # Each token's offset in its chunk and its sequence's rows.
            sid = meta.q_seq_ids
            pos = meta.q_positions
            off = (pos - meta.chunk_starts[sid])[:, None]
            rows = state[seq.slots[sid]]  # [T, 2, H] = (z_{n-2}, z_{n-1})
            zp = jnp.pad(z, ((2, 0), (0, 0)))
            pos = pos[:, None]
            prev1 = jnp.where(
                off >= 1, zp[1 : t + 1], jnp.where(pos >= 1, rows[:, 1], 0)
            )
            prev2 = jnp.where(
                off >= 2,
                zp[:t],
                jnp.where(
                    pos >= 2, jnp.where(off == 1, rows[:, 1], rows[:, 0]), 0
                ),
            )
            taps = layer["conv_w"].astype(jnp.float32)
            conv = (
                taps[0] * prev2.astype(jnp.float32)
                + taps[1] * prev1.astype(jnp.float32)
                + taps[2] * z.astype(jnp.float32)
            )
            y = (c.astype(jnp.float32) * conv).astype(x.dtype)
            x = x + linear(y, layer["out_proj"])
            # What each sequence leaves behind: its last token's
            # predecessor (by the rules above) and that token.
            new = jnp.stack(
                [prev1[seq.last_rows], z[seq.last_rows]], axis=1
            )
            state = state.at[seq.slots].set(
                new.astype(state.dtype), mode="drop"
            )
        return x, state

    def _attention(self, x, layer, kv, meta, inv_freq, attn_fn, kv_write_fn):
        t = x.shape[0]
        nh, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope(SCOPE_ATTN_QKV):
            u = rms_norm(x, layer["op_ln"], self.rms_eps)
            q = linear(u, layer["wq"]).reshape(t, nh, d)
            k = linear(u, layer["wk"]).reshape(t, nkv, d)
            v = linear(u, layer["wv"]).reshape(t, nkv, d)
            q = rms_norm(q, layer["q_norm"], self.rms_eps)
            k = rms_norm(k, layer["k_norm"], self.rms_eps)
            q = apply_rope(q, meta.q_positions, inv_freq)
            k = apply_rope(k, meta.q_positions, inv_freq)
        with jax.named_scope(SCOPE_KV_WRITE):
            kv = kv_write_fn(kv, k, v, meta.slot_mapping)
        with jax.named_scope(SCOPE_ATTN):
            attn = attn_fn(
                q, kv, meta, scale=self.scale, num_kv_heads=nkv
            )
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + linear(attn.reshape(t, -1), layer["wo"])
        return x, kv

    def _route(self, u, layer: dict):
        """Router, float32 throughout: (indices [T, k], weights [T, k])."""
        logits = jnp.dot(
            u.astype(jnp.float32),
            layer["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        scores = jax.nn.sigmoid(logits)
        chosen_by = scores
        if self.use_expert_bias:
            chosen_by = scores + layer["expert_bias"]
        _, idx = jax.lax.top_k(chosen_by, self.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if self.norm_topk:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
        return idx.astype(jnp.int32), w * self.routed_scale

    def moe_impl(self, layer: dict, tokens: int) -> str:
        """What the routed layers of a step of ``tokens`` rows are
        traced with: "dense" | "ragged" | "grouped"."""
        from vllm_distributed_tpu import envs

        return select_moe_impl(
            envs.VDT_MOE_IMPL, tokens * self.top_k, layer["w1"],
            on_mesh=False, unquantized_backend=self.kernel_backend,
        )

    def _moe(self, u, layer: dict, live):
        """(the routed layer's output [T, H], picks [2] int32 by
        PICK_KINDS over the live rows)."""
        with jax.named_scope(SCOPE_MOE_ROUTER):
            idx, w = self._route(u, layer)
            onehot = idx[..., None] == jnp.arange(
                self.num_experts, dtype=jnp.int32
            )
            counts = (onehot & live[:, None, None]).sum(
                axis=(0, 1), dtype=jnp.int32
            )
            picks = jnp.stack([counts.sum(), counts.max()])
        out = routed_experts(
            self.moe_impl(layer, u.shape[0]), u, idx, w,
            layer["w1"], layer["w3"], layer["w2"], live=live,
            interpret=self.kernel_backend == "pallas_interpret",
        )
        return out, picks

    def _ffn(self, u, layer: dict):
        return linear(
            jax.nn.silu(linear(u, layer["gate"])) * linear(u, layer["up"]),
            layer["down"],
        )

    def forward(
        self,
        params: dict,
        token_ids: jax.Array,  # [T]
        kv_caches: list,  # one per attention layer: [2, P, page, HD]
        meta: AttentionMetadata,
        attn_fn: Callable = paged_attention_reference,
        kv_write_fn: Callable = write_kv_pages,
        return_hidden: bool = False,
        stats: dict | None = None,
        seq_state: SeqState | None = None,
    ) -> tuple:
        """Returns (logits [S, V] at meta.logits_indices, updated
        caches, updated sequence states); with ``stats`` a dict, also
        leaves the router's pick counts there (``stats["moe_picks"]``:
        [2] int32 by PICK_KINDS, summed over the routed layers)."""
        assert seq_state is not None, "lfm2_moe needs its sequence state"
        with jax.named_scope(SCOPE_EMBED):
            x = params["embed"][token_ids].astype(self.dtype)
        inv_freq = rope_frequencies(self.head_dim, self.rope_theta)
        live = meta.q_seq_ids < meta.seq_lens.shape[0]
        new_kv, new_states = [], []
        picks = jnp.zeros(len(PICK_KINDS), jnp.int32)
        for i, (kind, layer) in enumerate(
            zip(self.layer_types, params["layers"])
        ):
            if kind == "conv":
                x, state = self._short_conv(
                    x, layer, seq_state.states[len(new_states)], seq_state,
                    meta,
                )
                new_states.append(state)
            else:
                x, kv = self._attention(
                    x, layer, kv_caches[len(new_kv)], meta, inv_freq,
                    attn_fn, kv_write_fn,
                )
                new_kv.append(kv)
            with jax.named_scope(SCOPE_MLP):
                u = rms_norm(x, layer["ffn_ln"], self.rms_eps)
                if i < self.num_dense:
                    x = x + self._ffn(u, layer)
            if i >= self.num_dense:
                m, layer_picks = self._moe(u, layer, live)
                picks = picks + layer_picks
                with jax.named_scope(SCOPE_MLP):
                    x = x + m
        if stats is not None:
            stats["moe_picks"] = picks

        with jax.named_scope(SCOPE_LM_HEAD):
            x = rms_norm(x, params["norm"], self.rms_eps)
            sel = x[meta.logits_indices]  # [S, H]
            logits = sel @ params["embed"].T.astype(sel.dtype)
        logits = logits.astype(jnp.float32)
        if return_hidden:
            return logits, new_kv, new_states, sel.astype(jnp.float32)
        return logits, new_kv, new_states
