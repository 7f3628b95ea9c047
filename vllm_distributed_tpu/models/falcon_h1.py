"""Falcon-H1 decoder (tiiuae Falcon-H1-34B-Instruct): in every layer a
Mamba-2 mixer *beside* grouped-query attention, both reading one normed
input, then a SwiGLU MLP; a multiplier on nearly every branch.

With ``e`` the embedding row, per layer (all norms RMS with a weight)::

    x_0 = e · embedding_multiplier
    u   = norm_in(x)
    x   = x + Mixer(u · ssm_in_multiplier) · ssm_out_multiplier
            + Attn(u · attention_in_multiplier) · attention_out_multiplier
    v   = norm_ff(x)
    x   = x + W_down(W_up v ⊙ silu(W_gate v · mlp_multipliers[0]))
              · mlp_multipliers[1]
    logits = W_head · norm_f(x_L) · lm_head_multiplier      (head untied)

``Attn``: ``q, k, v`` without bias (``num_attention_heads`` /
``num_key_value_heads`` heads of ``head_dim``); ``k ← k ·
key_multiplier``; RoPE by half-rotation, no scaling; causal softmax at
``head_dim^-½`` over the paged cache; ``o_proj``.

``Mixer`` (Mamba-2: ``d_ssm`` = ``mamba_d_ssm``, ``H`` heads of ``P``,
``G`` groups of ``N`` = ``mamba_d_state``, ``mamba_d_conv`` taps)::

    p            = (W_in · u') ⊙ m      m = ssm_multipliers over (z|x|B|C|dt)
    z | xBC | dt = split(p, [d_ssm, d_ssm + 2·G·N, H])
    xBC_t        = silu(Σ_j w_j ⊙ xBC_{t-3+j} + b)     depthwise, causal
    x | B | C    = split(xBC, [d_ssm, G·N, G·N])
    Δ_t          = softplus(dt_t + dt_bias)             (no clamp)
    S_t[h]       = exp(Δ_t[h]·A[h]) · S_{t-1}[h] + Δ_t[h] · x_t[h] ⊗ B_t[g(h)]
    y_t[h]       = S_t[h] · C_t[g(h)] + D[h] · x_t[h]      A = −exp(A_log)
    y            = y ⊙ silu(z);  y ← grouped RMS norm over the G groups ⊙ w
    out          = W_out · y

(``mamba_rms_norm`` true and ``mamba_norm_before_gate`` false: the gate
first, then the norm over each group's ``d_ssm / G`` lanes.)  This is
the installed ``transformers`` ``modeling_falcon_h1.py`` read line by
line (``FalconH1Mixer.torch_forward``, ``FalconH1RMSNormGated``,
``compute_mup_vector``, ``FalconH1DecoderLayer``); nothing is imported
from it.  Where that file and these lines disagree, the file wins.

**The state of a sequence after token t**, per layer, stated by
``seq_state_layout()`` as two kinds beside the paged cache of
``kv_cache_layout()``: ``conv`` — ``(xBC_{t-2}, xBC_{t-1}, xBC_t)`` as
it enters the convolution (after ``m``), in the model's dtype — and
``ssm`` — ``S_t`` [H, P, N], stored in ``ssm_state_dtype`` (the model's
dtype unless the config says otherwise; the update's arithmetic is
float32 whatever the storage).  A chunk that starts mid-sequence reads
both from the sequence's row; a sequence at position 0 starts from
zeros without any row being cleared.  The recurrence runs in
``ops/ssm.py``: chunked over a single step's flat ragged batch, one
multiply-add a row where every sequence has one token (the fused scan).
Under the Pallas backend the chunked form runs as one kernel a layer
(``ops/pallas/ssd_chunk_scan.py``, ``vdt_ssd_chunk_scan``) where the
shapes are the ones it is written for: the plain form stays the oracle
and the path of every other backend and shape.

Seeded weights only (``--load-format dummy``).  The published
multipliers are made for trained weights; ``init_params`` draws each
matrix at a spread that undoes the multiplier on its output, so that
every branch carries weight in a check on random weights (the recipe is
stated there, and repeated by ``benchmark/reference/falcon_h1.py``).

Not supported, and refused at load by name: a mesh (``validate_mesh``),
and with any per-sequence state: prefix caching, speculative decoding,
the host spill tier, KV transfer (worker/model_runner.py).
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (
    SCOPE_ATTN,
    SCOPE_ATTN_OUT,
    SCOPE_ATTN_QKV,
    SCOPE_EMBED,
    SCOPE_KV_WRITE,
    SCOPE_LM_HEAD,
    SCOPE_MLP,
    SCOPE_SSM_MIXER,
    SCOPE_SSM_SCAN,
    SupportsQuantization,
    apply_rope,
    linear,
    rms_norm,
    rope_frequencies,
)
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    KVCacheLayout,
    SeqState,
    SeqStateLayout,
    StateKind,
    paged_attention_reference,
    write_kv_pages,
)
from vllm_distributed_tpu.ops.pallas import ssd_chunk_scan as ssd_kernel
from vllm_distributed_tpu.ops.ssm import (
    ragged_causal_conv,
    ssd_chunk_scan,
    ssm_update,
)
from vllm_distributed_tpu.utils import cdiv

# The seeded recipe (benchmark/reference/falcon_h1.py states the same
# numbers).  A matrix [in, out] is ``normal × gain · in^-½ / m``, ``m``
# the product of the multipliers between its input and its output, so
# that a branch's output has about its input's spread whatever the
# widths; the gains sharpen the attention's softmax and lift its branch
# to the others'.  The rest is the published initialisation's shape.
QK_GAIN = 1.5  # q_proj and k_proj each: scores of spread ~2.2
WO_GAIN = 2.0  # o_proj: an average over the context is below unit spread
DT_MIN, DT_MAX = 0.001, 0.1  # dt_bias: inverse softplus of log-uniform
A_MIN, A_MAX = 1.0, 16.0  # A_log = log of uniform
CONV_BIAS_STD = 0.02
KEYS_PER_LAYER = 17


@contextlib.contextmanager
def _scan_scope():
    """``ssm_scan`` inside ``ssm_mixer``, from outside both."""
    with jax.named_scope(SCOPE_SSM_MIXER), jax.named_scope(SCOPE_SSM_SCAN):
        yield


def inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class FalconH1ForCausalLM(SupportsQuantization):
    architectures = ("FalconH1ForCausalLM",)
    QUANT_PARAMS = frozenset({
        "wq", "wk", "wv", "wo", "in_proj", "out_proj", "gate", "up", "down",
        "lm_head",
    })
    # one single-step program a (tokens, longest chunk) bucket, as
    # Lfm2MoeForCausalLM: the state arrays have the bucket's rows anyway
    pin_step_seq_bucket = True
    # ... and one whether or not a request of the step reads logprobs:
    # a single step always computes the log-softmax of its 64 rows (a
    # few passes over 67 MB in a step of tens of milliseconds), the
    # host fetches it only for a step that wants it.  A program of its
    # own for such steps is half a minute of compiling each, first met
    # after warm-up by whoever sends the first ``logprobs`` request at
    # a token bucket (the benchmark's check does, after its window).
    step_logprobs_always = True

    def __init__(self, model_config: Any) -> None:
        hf = model_config.hf_config
        self.model_type = hf.model_type
        self.num_layers = model_config.get_num_layers()
        self.hidden_size = int(hf.hidden_size)
        self.num_heads = int(hf.num_attention_heads)
        self.num_kv_heads = int(hf.num_key_value_heads)
        self.head_dim = model_config.get_head_dim()
        self.intermediate_size = int(hf.intermediate_size)
        self.vocab_size = int(hf.vocab_size)
        self.rope_theta = float(getattr(hf, "rope_theta", 1e11))
        self.rms_eps = float(getattr(hf, "rms_norm_eps", 1e-5))
        self.d_ssm = int(
            hf.mamba_d_ssm
            if getattr(hf, "mamba_d_ssm", None) is not None
            else hf.mamba_expand * hf.hidden_size
        )
        self.ssm_heads = int(hf.mamba_n_heads)
        self.ssm_head_dim = int(hf.mamba_d_head)
        self.ssm_groups = int(hf.mamba_n_groups)
        self.ssm_state = int(hf.mamba_d_state)
        self.conv_taps = int(hf.mamba_d_conv)
        self.ssm_chunk_size = int(hf.mamba_chunk_size)
        self.conv_dim = self.d_ssm + 2 * self.ssm_groups * self.ssm_state
        for what, bad in (
            ("rope_scaling", getattr(hf, "rope_scaling", None)),
            ("attention_bias", getattr(hf, "attention_bias", False)),
            ("mlp_bias", getattr(hf, "mlp_bias", False)),
            ("mamba_proj_bias", getattr(hf, "mamba_proj_bias", False)),
            ("projectors_bias", getattr(hf, "projectors_bias", False)),
            ("tie_word_embeddings", getattr(hf, "tie_word_embeddings", False)),
            ("mamba_norm_before_gate",
             getattr(hf, "mamba_norm_before_gate", False)),
            ("mamba_rms_norm false", not getattr(hf, "mamba_rms_norm", True)),
            ("mamba_conv_bias false",
             not getattr(hf, "mamba_conv_bias", True)),
            ("mamba_d_ssm != mamba_n_heads * mamba_d_head",
             self.d_ssm != self.ssm_heads * self.ssm_head_dim),
            ("mamba_n_heads not a multiple of mamba_n_groups",
             self.ssm_heads % self.ssm_groups),
        ):
            if bad:
                raise NotImplementedError(
                    f"falcon_h1: {what}: the block is written for the "
                    "published Falcon-H1 configs, which have none"
                )
        # in_proj's five segments (z, x, B, C, dt): (multiplier, width)
        gn = self.ssm_groups * self.ssm_state
        self.mup = tuple(zip(
            map(float, hf.ssm_multipliers),
            (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads),
        ))
        self.mup_vector = np.concatenate(
            [np.full(width, m, np.float32) for m, width in self.mup]
        )
        self.embedding_multiplier = float(hf.embedding_multiplier)
        self.lm_head_multiplier = float(hf.lm_head_multiplier)
        self.key_multiplier = float(hf.key_multiplier)
        self.attn_in_multiplier = float(hf.attention_in_multiplier)
        self.attn_out_multiplier = float(hf.attention_out_multiplier)
        self.ssm_in_multiplier = float(hf.ssm_in_multiplier)
        self.ssm_out_multiplier = float(hf.ssm_out_multiplier)
        self.mlp_multipliers = tuple(float(v) for v in hf.mlp_multipliers)
        self.dtype = jnp.dtype(model_config.dtype)
        self.state_dtype = jnp.dtype(
            getattr(hf, "ssm_state_dtype", None) or self.dtype
        )
        self.scale = self.head_dim**-0.5
        # The backend a chunked single step's recurrence may use: the
        # kernel where Pallas runs and the shapes are the ones it is
        # written for (``_scan_in_kernel``), the plain form elsewhere.
        from vllm_distributed_tpu import envs
        from vllm_distributed_tpu.ops import resolve_kernel_backend

        self.kernel_backend = resolve_kernel_backend(envs.VDT_USE_PALLAS)
        self._init_quant(model_config)

    # ---- what the runner reads of the caches and the state ----
    def kv_cache_layout(self) -> KVCacheLayout:
        return KVCacheLayout.by_heads(
            self.num_layers, self.num_kv_heads, self.head_dim
        )

    def seq_state_layout(self) -> SeqStateLayout:
        """Two kinds a layer: the last ``mamba_d_conv - 1`` rows that
        entered the convolution, and the state-space state."""
        return SeqStateLayout(kinds=(
            StateKind(
                "conv", (self.conv_taps - 1, self.conv_dim), self.num_layers
            ),
            StateKind(
                "ssm",
                (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                self.num_layers,
                self.state_dtype.name,
            ),
        ))

    def kv_cache_spec(self) -> P:
        return P(None, None, None, None)

    def validate_mesh(self, mesh) -> None:
        if mesh.devices.size > 1:
            raise ValueError(
                f"falcon_h1 cannot run on a mesh of {mesh.devices.size} "
                "devices: its per-sequence state (the mixer's heads and "
                "groups) has no partition rule yet (models/falcon_h1.py)"
            )

    def step_max_q_class(self, max_q: int, tokens: int) -> int:
        """The longest-chunk bucket a single step of ``tokens`` rows is
        compiled for: 1 where every sequence has one token (the
        one-token update), else the token bucket itself.  The scan's
        shapes are the token bucket's whatever the chunks' lengths,
        and the attention kernel's query tile is full at 128 tokens for
        these heads (beyond it ``max_q`` only bounds a tile list that
        the rows bound as well; below it a short chunk fills part of a
        larger tile, in a step whose time is the weights').  So a step
        program is one a token bucket, not one a (token bucket, longest
        chunk) pair: the benchmark cell's sweep meets 8 programs where
        it met 33, each of which takes half a minute to compile at
        these widths (PERF.md, PR 47)."""
        return 1 if max_q == 1 else tokens

    # ---- params ----
    def init_params(self, rng: jax.Array) -> dict:
        """Seeded weights (tests, --load-format dummy): one root key
        split into ``17 · layers + 2``.  Layer ``i`` takes keys ``17i
        ..``: wq, wk, wv, wo; in_proj's five segments (z, x, B, C, dt:
        drawn apart and laid side by side); the taps (``normal ×
        taps^-½``) and their bias (``normal × 0.02``); ``dt_bias`` (the
        inverse softplus of ``exp(uniform(ln 0.001, ln 0.1))``);
        ``A_log`` (``ln uniform(1, 16)``); out_proj; gate, up, down.
        Then the embedding (``normal / embedding_multiplier``) and the
        head.  A matrix [in, out] is ``normal × gain · in^-½ / m``,
        ``m`` the multipliers on its path (q, v: attention_in; k:
        attention_in · key; o: attention_out; an in_proj segment:
        ssm_in · its ssm_multiplier; out_proj: ssm_out; gate, down: the
        mlp's two; the head: lm_head), ``gain`` 1.5 for q and k, 2 for
        o, 1 elsewhere; ``D`` and the norms are ones.  Every matrix is
        rounded to the model dtype; ``dt_bias``, ``A_log`` and ``D``
        stay float32."""
        h, nh, nkv, d = (
            self.hidden_size, self.num_heads, self.num_kv_heads,
            self.head_dim,
        )
        im, hs = self.intermediate_size, self.ssm_heads
        keys = jax.random.split(rng, KEYS_PER_LAYER * self.num_layers + 2)

        def nrm(key, shape, std):
            return (jax.random.normal(key, shape, jnp.float32) * std).astype(
                self.dtype
            )

        def mat(key, shape, mult, gain=1.0):
            return nrm(key, shape, gain * shape[0] ** -0.5 / mult)

        def ones(n):
            return jnp.ones((n,), self.dtype)

        def uniform(key, lo, hi):
            return jax.random.uniform(
                key, (hs,), jnp.float32, minval=lo, maxval=hi
            )

        a_in, g0, g1 = self.attn_in_multiplier, *self.mlp_multipliers
        layers = []
        for i in range(self.num_layers):
            k = keys[KEYS_PER_LAYER * i:]
            layers.append({
                "in_ln": ones(h), "ff_ln": ones(h),
                "wq": mat(k[0], (h, nh * d), a_in, QK_GAIN),
                "wk": mat(k[1], (h, nkv * d), a_in * self.key_multiplier,
                          QK_GAIN),
                "wv": mat(k[2], (h, nkv * d), a_in),
                "wo": mat(k[3], (nh * d, h), self.attn_out_multiplier,
                          WO_GAIN),
                "in_proj": jnp.concatenate([
                    mat(k[4 + seg], (h, width), self.ssm_in_multiplier * m)
                    for seg, (m, width) in enumerate(self.mup)
                ], axis=1),
                "conv_w": nrm(k[9], (self.conv_taps, self.conv_dim),
                              self.conv_taps**-0.5),
                "conv_b": nrm(k[10], (self.conv_dim,), CONV_BIAS_STD),
                "dt_bias": inv_softplus(jnp.exp(uniform(
                    k[11], math.log(DT_MIN), math.log(DT_MAX)))),
                "a_log": jnp.log(uniform(k[12], A_MIN, A_MAX)),
                "d_skip": jnp.ones((hs,), jnp.float32),
                "ssm_norm": ones(self.d_ssm),
                "out_proj": mat(k[13], (self.d_ssm, h),
                                self.ssm_out_multiplier),
                "gate": mat(k[14], (h, im), g0),
                "up": mat(k[15], (h, im), 1.0),
                "down": mat(k[16], (im, h), g1),
            })
        return {
            "embed": nrm(keys[-2], (self.vocab_size, h),
                         1.0 / self.embedding_multiplier),
            "lm_head": mat(keys[-1], (h, self.vocab_size),
                           self.lm_head_multiplier),
            "layers": layers,
            "norm": ones(h),
        }

    def partition_specs(self) -> dict:
        """Everything replicated: a mesh is refused (validate_mesh)."""
        shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    def map_hf_name(self, name: str):
        raise NotImplementedError(
            "falcon_h1 loads seeded weights only (--load-format dummy): "
            "reshaping the published mamba.conv1d.weight [C, 1, 4] into "
            "taps and transposing the projections is not written"
        )

    def fuse_quantized_projections(self, params: dict) -> dict:
        return params

    # ---- forward ----
    def _scan_in_kernel(self, tokens: int) -> bool:
        """Whether a chunked single step of ``tokens`` rows runs its
        recurrence in ``vdt_ssd_chunk_scan``: by backend and by shape,
        nothing else."""
        return self.kernel_backend != "reference" and ssd_kernel.accepts(
            self.ssm_head_dim, self.ssm_state, tokens, self.ssm_chunk_size
        )

    def ssm_tokens_walked(self, chunk_lens, tokens: int) -> int:
        """Token slots the recurrence's matrix products run over in a
        chunked single step of ``tokens`` rows whose sequences have
        ``chunk_lens`` tokens, in batch order: every tile of the bucket
        in the plain form, each sequence's own windows in the kernel
        (``step_counts``' ``ssm_tokens_tiled``)."""
        if self._scan_in_kernel(tokens):
            return ssd_kernel.walked_tokens(chunk_lens, self.ssm_chunk_size)
        tile = min(self.ssm_chunk_size, tokens)
        return tile * cdiv(tokens, tile)

    def step_counts(self, shape, params) -> tuple[dict, dict]:
        """What a step the runner hands over (``ops.attention.StepShape``)
        says of the chunked scans: a chunked single step's own tokens
        (the ``runner.dispatch`` span's ``ssm_tokens_live``) and the
        token slots its scans' matrix products run over
        (``ssm_tokens_tiled``), which are the worker counter's live |
        tiled.  A step in which every sequence has one token runs the
        one-token update and says nothing; so does a fused scan."""
        if shape.kind != "prefill" or shape.max_q_pad == 1:
            return {}, {}
        live = sum(shape.num_new)
        tiled = self.ssm_tokens_walked(shape.num_new, shape.rows_pad)
        return (
            {"ssm_tokens_live": live, "ssm_tokens_tiled": tiled},
            {"ssm_chunk_tokens": {"live": live, "tiled": tiled}},
        )

    def _mixer_in(self, u, layer: dict, conv_state, seq: SeqState, meta):
        """The mixer up to the recurrence: (the gate z [T, d_ssm]
        float32, the recurrence's inputs (x, Δ, A, B, C, each token's
        state row), the layer's convolution state with this step's
        sequences' rows rewritten)."""
        t = u.shape[0]
        f32 = jnp.float32
        hs, hp, g, n = (
            self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
            self.ssm_state,
        )
        p = linear(u * self.ssm_in_multiplier, layer["in_proj"]).astype(f32)
        p = p * self.mup_vector
        z = p[:, : self.d_ssm]
        # what enters the convolution is what the state keeps: rounded
        # to the model dtype here, so that a predecessor read from the
        # batch and one read from a row are the same number
        xbc = p[:, self.d_ssm : self.d_ssm + self.conv_dim].astype(u.dtype)
        dt = jax.nn.softplus(p[:, -hs:] + layer["dt_bias"])  # [T, H]
        sid = meta.q_seq_ids
        s = seq.slots.shape[0]
        live = sid < s
        tok_seq = jnp.minimum(sid, s - 1)
        rows = conv_state.shape[0]
        tok_rows = jnp.where(live, seq.slots[tok_seq], rows)
        pos = meta.q_positions
        conv, prevs = ragged_causal_conv(
            xbc,
            conv_state[jnp.minimum(tok_rows, rows - 1)],
            (pos - meta.chunk_starts[tok_seq])[:, None],
            pos[:, None],
            layer["conv_w"].astype(f32),
        )
        xbc_c = jax.nn.silu(conv + layer["conv_b"].astype(f32)).astype(
            u.dtype
        )
        # what each sequence leaves behind: its last token's nearer
        # predecessors (oldest first) and that token
        new_conv = jnp.stack(
            [prev[seq.last_rows] for prev in reversed(prevs[:-1])]
            + [xbc[seq.last_rows]],
            axis=1,
        )
        conv_state = conv_state.at[seq.slots].set(
            new_conv.astype(conv_state.dtype), mode="drop"
        )
        x = xbc_c[:, : self.d_ssm].reshape(t, hs, hp)
        b = xbc_c[:, self.d_ssm : self.d_ssm + g * n].reshape(t, g, n)
        c = xbc_c[:, self.d_ssm + g * n :].reshape(t, g, n)
        a = -jnp.exp(layer["a_log"])
        return z, (x, dt, a, b, c, tok_rows), conv_state

    def _recurrence(self, inputs, layer: dict, ssm_state, seq: SeqState,
                    meta):
        """(y [T, H, P] float32, the layer's state-space state with this
        step's sequences' rows rewritten).  It names its own scopes:
        ``ssm_scan`` inside ``ssm_mixer``, but for the kernel's call,
        which lies under neither (a trace files a kernel's time under
        its name and under the scope around it: under both it would be
        counted twice)."""
        x, dt, a, b, c, tok_rows = inputs
        chunked = dict(
            seq_ids=meta.q_seq_ids, positions=meta.q_positions,
            chunk_starts=meta.chunk_starts, slots=seq.slots,
            last_rows=seq.last_rows, chunk=self.ssm_chunk_size,
        )
        if seq.max_q > 1 and self._scan_in_kernel(x.shape[0]):
            return ssd_kernel.ssd_chunk_scan(
                x, dt, a, b, c, layer["d_skip"], ssm_state, **chunked,
                scope=_scan_scope,
                interpret=self.kernel_backend == "pallas_interpret",
            )
        with _scan_scope():
            if seq.max_q == 1:
                return ssm_update(
                    x, dt, a, b, c, layer["d_skip"], ssm_state,
                    tok_rows, meta.q_positions == 0,
                )
            return ssd_chunk_scan(
                x, dt, a, b, c, layer["d_skip"], ssm_state, **chunked
            )

    def _mixer_out(self, y, z, layer: dict, dtype):
        """From the recurrence's output to the mixer's [T, hidden]
        (before ``ssm_out_multiplier``)."""
        y = self._gated_norm(y.reshape(y.shape[0], self.d_ssm), z, layer)
        return linear(y.astype(dtype), layer["out_proj"])

    def _gated_norm(self, y, z, layer: dict):
        """The gate first, then the RMS norm over each group's lanes
        (``mamba_norm_before_gate`` false), float32."""
        t, g = y.shape[0], self.ssm_groups
        y = (y * jax.nn.silu(z)).reshape(t, g, self.d_ssm // g)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + self.rms_eps
        )
        return y.reshape(t, self.d_ssm) * layer["ssm_norm"].astype(
            jnp.float32
        )

    def _attention(self, u, layer, kv, meta, inv_freq, attn_fn, kv_write_fn):
        """(the attention branch [T, hidden] before
        ``attention_out_multiplier``, the layer's cache)."""
        t = u.shape[0]
        nh, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope(SCOPE_ATTN_QKV):
            ua = u * self.attn_in_multiplier
            q = linear(ua, layer["wq"]).reshape(t, nh, d)
            k = linear(ua, layer["wk"]).reshape(t, nkv, d)
            k = k * self.key_multiplier
            v = linear(ua, layer["wv"]).reshape(t, nkv, d)
            q = apply_rope(q, meta.q_positions, inv_freq)
            k = apply_rope(k, meta.q_positions, inv_freq)
        with jax.named_scope(SCOPE_KV_WRITE):
            kv = kv_write_fn(kv, k, v, meta.slot_mapping)
        with jax.named_scope(SCOPE_ATTN):
            attn = attn_fn(q, kv, meta, scale=self.scale, num_kv_heads=nkv)
        with jax.named_scope(SCOPE_ATTN_OUT):
            out = linear(attn.reshape(t, -1), layer["wo"])
        return out, kv

    def forward(
        self,
        params: dict,
        token_ids: jax.Array,  # [T]
        kv_caches: list,  # one per layer: [2, P, page, HD]
        meta: AttentionMetadata,
        attn_fn: Callable = paged_attention_reference,
        kv_write_fn: Callable = write_kv_pages,
        return_hidden: bool = False,
        seq_state: SeqState | None = None,
    ) -> tuple:
        """Returns (logits [S, V] at meta.logits_indices, updated
        caches, updated sequence states)."""
        assert seq_state is not None, "falcon_h1 needs its sequence state"
        states = self.seq_state_layout().by_kind(seq_state.states)
        with jax.named_scope(SCOPE_EMBED):
            x = (
                params["embed"][token_ids].astype(jnp.float32)
                * self.embedding_multiplier
            ).astype(self.dtype)
        inv_freq = rope_frequencies(self.head_dim, self.rope_theta)
        g0, g1 = self.mlp_multipliers
        new_kv, new_conv, new_ssm = [], [], []
        for i, layer in enumerate(params["layers"]):
            with jax.named_scope(SCOPE_SSM_MIXER):
                u = rms_norm(x, layer["in_ln"], self.rms_eps)
                z, scan_in, conv_i = self._mixer_in(
                    u, layer, states["conv"][i], seq_state, meta
                )
            y, ssm_i = self._recurrence(
                scan_in, layer, states["ssm"][i], seq_state, meta
            )
            with jax.named_scope(SCOPE_SSM_MIXER):
                mixed = self._mixer_out(y, z, layer, u.dtype)
            new_conv.append(conv_i)
            new_ssm.append(ssm_i)
            attn, kv = self._attention(
                u, layer, kv_caches[i], meta, inv_freq, attn_fn, kv_write_fn
            )
            new_kv.append(kv)
            with jax.named_scope(SCOPE_ATTN_OUT):
                x = x + (
                    mixed.astype(jnp.float32) * self.ssm_out_multiplier
                    + attn.astype(jnp.float32) * self.attn_out_multiplier
                ).astype(x.dtype)
            with jax.named_scope(SCOPE_MLP):
                v = rms_norm(x, layer["ff_ln"], self.rms_eps)
                inner = linear(v, layer["up"]) * jax.nn.silu(
                    linear(v, layer["gate"]) * g0
                )
                x = x + linear(inner, layer["down"]) * g1

        with jax.named_scope(SCOPE_LM_HEAD):
            x = rms_norm(x, params["norm"], self.rms_eps)
            sel = x[meta.logits_indices]  # [S, H]
            logits = linear(sel, params["lm_head"]).astype(jnp.float32)
            logits = logits * self.lm_head_multiplier
        if return_hidden:
            return logits, new_kv, new_conv + new_ssm, sel.astype(jnp.float32)
        return logits, new_kv, new_conv + new_ssm
