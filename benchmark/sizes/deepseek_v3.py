"""Sizes of a DeepSeek-V3-architecture configuration as one chip of an
expert-parallel group holds it (``configs/gigachat3.1-702b-a36b-ep16-
bf16.json``), by the contract in ``benchmark/README.md``, from the
file's own keys: ``n_routed_experts`` is what this chip holds of each
routed layer's experts, ``expert_parallel_size`` how many chips share
them; the first ``first_k_dense_replace`` layers have a dense FFN, the
others a shared expert, a router over every routed expert of the
deployment and the held experts.  Everything bfloat16, two bytes a
value."""


LANES = 128


def kv_bytes_per_token(cfg: dict) -> int:
    """One latent row (``kv_lora_rank`` compressed values and the
    ``qk_rope_head_dim`` rotary part) a layer, bfloat16: key and value
    at once, no heads, whatever ``v_head_dim`` is.  On this chip a row
    holds whole 128-lane tiles of HBM (576 values lie in 640 lanes), so
    that is what a token holds here."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * (-(-row // LANES) * LANES) * 2


def routed_width(cfg: dict) -> int:
    """The router's outputs: every routed expert of the deployment."""
    return cfg["n_routed_experts"] * cfg.get("expert_parallel_size", 1)


def routed_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def experts_touched(cfg: dict, batch: float) -> float:
    """Experts of one layer, of those this chip holds, that ``batch``
    rows reach when each makes ``num_experts_per_tok`` picks evenly
    over the router's whole width (the groups leave an even router
    even): held * (1 - (1 - k / width) ** batch)."""
    held, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return held * (1.0 - (1.0 - k / routed_width(cfg)) ** max(batch, 1.0))


def mla_params(cfg: dict) -> int:
    """One attention sublayer: q down and up, kv down, kv up, output."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (h * ql + ql * nh * (nope + rope) + h * (kvl + rope)
            + kvl * nh * (nope + vd) + nh * vd * h)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return cfg.get("n_shared_experts", 0) * expert_params(cfg)


def outside_experts_params(cfg: dict) -> int:
    """Every matrix a micro-step reads whatever its batch: the
    attention sublayers, the leading dense FFNs, each routed layer's
    shared expert and router (read as stored; its float32 copy is made
    on the chip), the output head.  The embedding is gathered, a row a
    sequence, and is left out; norms and the router's bias are
    thousands of values."""
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * mla_params(cfg)
            + cfg["first_k_dense_replace"] * 3 * h * cfg["intermediate_size"]
            + routed_layers(cfg) * (shared_params(cfg) + h * routed_width(cfg))
            + h * cfg["vocab_size"])


def weight_params(cfg: dict) -> int:
    """Everything the chip holds: the above, every held expert and the
    embedding."""
    return (outside_experts_params(cfg)
            + routed_layers(cfg) * cfg["n_routed_experts"]
            * expert_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def decode_weight_bytes(cfg: dict, batch: float = 1.0) -> float:
    """Everything outside the routed experts, and in every routed layer
    the held experts that ``batch`` sequences' picks *reach* in
    expectation (13.9 of 16 at 64 sequences): a form that reads every
    held expert whatever the picks reads more than this, never less, so
    the share of HBM's peak it gives cannot pass 100."""
    return 2.0 * (outside_experts_params(cfg) + routed_layers(cfg)
                  * experts_touched(cfg, batch) * expert_params(cfg))
