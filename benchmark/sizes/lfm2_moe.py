"""Sizes of an LFM2-MoE configuration (``configs/lfm2-8b-a1b-cut-
bf16.json``), by the contract in ``benchmark/README.md``, from the
file's own keys: ``layer_types`` says which layers hold a paged cache
(``full_attention``) and which a gated short convolution with two rows
of state a *sequence* (``conv``); the first ``num_dense_layers`` layers
have a dense FFN, the others ``num_experts`` experts of which a token
picks ``num_experts_per_tok``.  Everything bfloat16, two bytes a
value."""


def head_dim(cfg: dict) -> int:
    """The published file has no ``head_dim``."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layers_of(cfg: dict, kind: str) -> int:
    return sum(1 for k in cfg["layer_types"] if k == kind)


def kv_bytes_per_token(cfg: dict) -> int:
    """A key and a value of every KV head in every *attention* layer.
    The convolutions' state is a sequence's, whatever its length, and is
    not in it (``state_bytes_per_seq``)."""
    return (layers_of(cfg, "full_attention") * 2
            * cfg["num_key_value_heads"] * head_dim(cfg) * 2)


def state_bytes_per_seq(cfg: dict) -> int:
    """The last ``conv_L_cache - 1`` gated rows of every convolution
    layer: what one sequence holds beside its pages."""
    return (layers_of(cfg, "conv") * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * 2)


def experts_touched(cfg: dict, batch: float) -> float:
    """Distinct experts of one layer that ``batch`` sequences reach
    when each picks ``num_experts_per_tok`` of ``num_experts`` evenly:
    E * (1 - (1 - k / E) ** batch); 31.99 of 32 at 64 sequences."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** max(batch, 1.0))


def conv_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    return h * 3 * h + h * h + cfg["conv_L_cache"] * h


def attention_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], head_dim(cfg)
    return (2 * h * cfg["num_attention_heads"] * d
            + 2 * h * cfg["num_key_value_heads"] * d)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def outside_experts_params(cfg: dict) -> int:
    """Every matrix a micro-step reads whatever its batch: the
    operators, the dense FFNs, the routers, the head (tied to the
    embedding, which is read once as the head; the gather of a row a
    sequence is left out).  Norms and the router's bias are thousands
    of values."""
    h = cfg["hidden_size"]
    return (layers_of(cfg, "conv") * conv_params(cfg)
            + layers_of(cfg, "full_attention") * attention_params(cfg)
            + cfg["num_dense_layers"] * 3 * h * cfg["intermediate_size"]
            + routed_layers(cfg) * h * cfg["num_experts"]
            + h * cfg["vocab_size"])


def decode_weight_bytes(cfg: dict, batch: float = 1.0) -> float:
    """Everything outside the experts, and in every routed layer the
    experts that ``batch`` sequences' picks reach in expectation: all
    32 from about 40 sequences on."""
    return 2.0 * (outside_experts_params(cfg) + routed_layers(cfg)
                  * experts_touched(cfg, batch) * expert_params(cfg))
