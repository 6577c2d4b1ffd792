"""The program a configuration names with ``"program": "lfm2_moe"``:
``paddle_tpu/models/lfm2_moe.py`` (gated short convolutions whose state is
a two-row tail a slot in the layers ``layer_types`` calls ``conv``, softmax
attention over grouped K and V with q and k normed a head and rotated in
the others, two leading dense layers, then sparse experts without a shared
one, a head tied to the embedding), at whatever sizes the configuration
states. Serving only: the model has no backward. ``programs/llama.py``'s
docstring is the contract."""


def build(cfg):
    from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM
    layers = int(cfg["num_hidden_layers"])
    # what the program does not compute is refused by name, not guessed
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", True)):
        if cfg[key] != want:
            raise ValueError(f"lfm2_moe: {key}={cfg[key]!r} is not "
                             f"written (the program computes {want!r})")
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"lfm2_moe: rope_parameters.rope_type="
                         f"{rope['rope_type']!r} is not written (the "
                         f"program rotates by the plain theta^(-2i/d))")
    if len(cfg["layer_types"]) < layers:
        raise ValueError(f"lfm2_moe: layer_types names "
                         f"{len(cfg['layer_types'])} layers, the depth is "
                         f"{layers}")
    odd = sorted(set(cfg["layer_types"][:layers])
                 - {"conv", "full_attention"})
    if odd:
        raise ValueError(f"lfm2_moe: layer_types {odd} is not written (a "
                         f"layer is conv or full_attention)")
    hidden, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return Lfm2MoeForCausalLM(Lfm2MoeConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=hidden,
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=layers,
        layer_types=tuple(cfg["layer_types"][:layers]),
        conv_L_cache=int(cfg["conv_L_cache"]),
        num_attention_heads=heads,
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or hidden // heads),
        rope_theta=float(rope["rope_theta"]),
        num_dense_layers=int(cfg["num_dense_layers"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=int(cfg["num_experts"]),
        num_experts_published=int(cfg.get("num_experts_published",
                                          cfg["num_experts"])),
        expert_offset=int(cfg.get("expert_offset", 0)),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        norm_eps=float(cfg["norm_eps"]),
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"])))


def partition(name, axis):
    raise NotImplementedError(
        "lfm2_moe is served on one chip: stages over chips with their "
        "exchange are not written (ROADMAP Queue 2)")
