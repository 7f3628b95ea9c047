"""Sizes of a LongCat-Flash configuration as one chip of an
expert-parallel group holds it (``configs/longcat-flash-chat-ep32-
bf16.json``), by the contract in ``benchmark/README.md``, from the
file's own keys: ``n_routed_experts`` is what this chip holds of each
layer's experts, ``expert_parallel_size`` how many chips share them."""


LANES = 128


def kv_bytes_per_token(cfg: dict) -> int:
    """One latent row (``kv_lora_rank`` compressed values and the
    ``qk_rope_head_dim`` rotary part) in each of a layer's two attention
    sublayers, bfloat16: key and value at once, no heads.  On this chip
    a row holds whole 128-lane tiles of HBM (576 values lie in 640
    lanes whatever the program calls the array, and the DMA engine
    moves no narrower slice), so that is what a token holds here."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return 2 * cfg["num_layers"] * (-(-row // LANES) * LANES) * 2


def router_width(cfg: dict) -> int:
    return (cfg["n_routed_experts"] * cfg.get("expert_parallel_size", 1)
            + cfg["zero_expert_num"])


def experts_touched(cfg: dict, batch: float) -> float:
    """Experts of one layer, of those this chip holds, that ``batch``
    sequences reach when each makes ``moe_topk`` picks evenly over the
    router's whole width (identity experts and the other chips' experts
    included): held * (1 - (1 - k / width) ** batch)."""
    held, k = cfg["n_routed_experts"], cfg["moe_topk"]
    return held * (1.0 - (1.0 - k / router_width(cfg)) ** max(batch, 1.0))


def mla_params(cfg: dict) -> int:
    """One attention sublayer: q down and up, kv down, kv up, output."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (h * ql + ql * nh * (nope + rope) + h * (kvl + rope)
            + kvl * nh * (nope + vd) + nh * vd * h)


def decode_weight_bytes(cfg: dict, batch: float = 1.0) -> float:
    """bfloat16, two bytes a weight: per layer two attention sublayers,
    two dense FFNs, the router (read as stored; its float32 copy is made
    on the chip) and the held experts that ``batch`` sequences reach;
    then the output head.  The embedding is gathered, a row a sequence,
    and is left out; norms and the router's bias are thousands of
    values."""
    h = cfg["hidden_size"]
    dense = 3 * h * cfg["ffn_hidden_size"]
    expert = 3 * h * cfg["expert_ffn_hidden_size"]
    layer = (2 * mla_params(cfg) + 2 * dense + h * router_width(cfg)
             + experts_touched(cfg, batch) * expert)
    return 2.0 * (cfg["num_layers"] * layer + h * cfg["vocab_size"])
