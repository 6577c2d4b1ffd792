"""The program a configuration names with ``"program": "solar_open2"``:
``paddle_tpu/models/solar_open2.py`` (gated GQA without positions over
paged K and V pools in the layers ``gqa_layers`` names, Kimi Delta
Attention with a recurrent state a slot in the others, sparse experts of
which this chip holds a share in every layer), at whatever sizes the
configuration states. Serving only: the model has no backward.
``programs/llama.py``'s docstring is the contract."""


def build(cfg):
    from paddle_tpu.models import SolarOpen2Config, SolarOpen2ForCausalLM
    lin = cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False),
                      ("kda_allow_neg_eigval", True),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False),
                      ("first_k_dense_replace", 0),
                      ("n_shared_experts", 1)):
        if cfg[key] != want:
            raise ValueError(f"solar_open2: {key}={cfg[key]!r} is not "
                             f"written (the program computes {want!r})")
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError(f"solar_open2: linear_attn_config.num_kv_heads="
                         f"{lin['num_kv_heads']!r} is not written (KDA's "
                         f"k and v have a head each)")
    return SolarOpen2ForCausalLM(SolarOpen2Config(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        num_hidden_layers=layers,
        num_attention_heads=int(cfg["num_attention_heads"]),
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        gqa_layers=tuple(i for i in cfg["gqa_layers"] if i < layers),
        linear_num_heads=int(lin["num_heads"]),
        linear_head_dim=int(lin["head_dim"]),
        short_conv_kernel_size=int(lin["short_conv_kernel_size"]),
        gate_low_rank=int(cfg["gate_low_rank"]),
        kda_beta_scale=2.0,
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        n_routed_experts=int(cfg["n_routed_experts"]),
        n_routed_experts_published=int(cfg["n_routed_experts_published"]),
        expert_offset=int(cfg["expert_offset"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        n_shared_experts=int(cfg["n_shared_experts"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"])))


def partition(name, axis):
    raise NotImplementedError(
        "solar_open2 is served on one chip: experts over chips with their "
        "exchange are not written (ROADMAP Queue 2)")
