"""Sizes of a Falcon-H1 configuration (``configs/falcon-h1-34b-cut-
bf16.json``), by the contract in ``benchmark/README.md``, from the
file's own keys: every layer has grouped-query attention with a paged
cache *and* a Mamba-2 mixer with two kinds of state a *sequence* (the
convolution's last ``mamba_d_conv - 1`` rows, the state-space state),
then a dense SwiGLU MLP; the embedding and the head are untied.
Weights, activations, the cache and the convolution's rows are
``torch_dtype`` (bfloat16, two bytes a value); the state-space state is
``ssm_state_dtype``."""

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def d_ssm(cfg: dict) -> int:
    if cfg.get("mamba_d_ssm") is not None:
        return cfg["mamba_d_ssm"]
    return cfg["mamba_expand"] * cfg["hidden_size"]


def conv_dim(cfg: dict) -> int:
    """What the convolution runs over: x, B and C side by side."""
    return d_ssm(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def kv_bytes_per_token(cfg: dict) -> int:
    """A key and a value of every KV head in every layer.  The mixer's
    state is a sequence's, whatever its length, and is not in it
    (``state_bytes_per_seq``)."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)


def conv_state_bytes_per_seq(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * (cfg["mamba_d_conv"] - 1)
            * conv_dim(cfg) * 2)


def ssm_state_bytes_per_seq(cfg: dict, dtype: str | None = None) -> int:
    """Heads x head size x state size values a layer, at the file's
    ``ssm_state_dtype`` (or ``dtype``: what the other choice costs)."""
    item = _ITEMSIZE[dtype or cfg.get("ssm_state_dtype") or "bfloat16"]
    return (cfg["num_hidden_layers"] * cfg["mamba_n_heads"]
            * cfg["mamba_d_head"] * cfg["mamba_d_state"] * item)


def state_bytes_per_seq(cfg: dict, dtype: str | None = None) -> int:
    """What one sequence holds beside its pages, both kinds."""
    return conv_state_bytes_per_seq(cfg) + ssm_state_bytes_per_seq(cfg, dtype)


def attention_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (2 * h * cfg["num_attention_heads"] * d
            + 2 * h * cfg["num_key_value_heads"] * d)


def mixer_params(cfg: dict) -> int:
    """in_proj (z, x, B, C, dt), out_proj, the taps and their bias, the
    gated norm's gains, ``A_log``, ``D`` and ``dt_bias``."""
    h, heads = cfg["hidden_size"], cfg["mamba_n_heads"]
    return (h * (d_ssm(cfg) + conv_dim(cfg) + heads) + d_ssm(cfg) * h
            + (cfg["mamba_d_conv"] + 1) * conv_dim(cfg) + d_ssm(cfg)
            + 3 * heads)


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: dict) -> int:
    """One layer's matrices (the two norms' gains are 2 x hidden more)."""
    return attention_params(cfg) + mixer_params(cfg) + mlp_params(cfg)


def vocab_params(cfg: dict) -> int:
    """The embedding, or the head: they are untied, so a chip that
    embeds and emits holds two."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * vocab_params(cfg))


def decode_weight_bytes(cfg: dict, batch: float = 1.0) -> float:
    """Every layer's matrices and the head, whatever the batch (a dense
    model: nothing is routed).  The embedding is gathered a row a
    sequence and left out; so is the state the mixer reads and writes,
    which is no weight (``kernel_model_falcon_h1.state_update``)."""
    return 2.0 * (cfg["num_hidden_layers"] * layer_params(cfg)
                  + vocab_params(cfg))
