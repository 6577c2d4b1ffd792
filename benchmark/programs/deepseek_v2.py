"""The program a configuration names with ``"program": "deepseek_v2"``:
``paddle_tpu/models/deepseek_v2.py`` (latent attention in every layer with
a compressed query and YaRN-scaled rotary positions over a paged latent
pool, a dense first layer, then routed experts, chosen by softmax scores
among the best groups, of which this chip holds a share), at whatever
sizes the configuration states. Serving only: the model has no backward.
``programs/llama.py``'s docstring is the contract."""


def build(cfg):
    from paddle_tpu.models import DeepseekV2Config, DeepseekV2ForCausalLM
    rope = cfg["rope_scaling"]
    # what the program does not compute is refused by name, not guessed
    for key, got, want in (
            ("scoring_func", cfg["scoring_func"], "softmax"),
            ("topk_method", cfg["topk_method"], "group_limited_greedy"),
            ("rope_scaling.type", rope["type"], "yarn"),
            ("norm_topk_prob", cfg["norm_topk_prob"], False),
            ("hidden_act", cfg["hidden_act"], "silu"),
            ("moe_layer_freq", cfg["moe_layer_freq"], 1),
            ("attention_bias", cfg["attention_bias"], False),
            ("tie_word_embeddings", cfg["tie_word_embeddings"], False)):
        if got != want:
            raise ValueError(f"deepseek_v2: {key}={got!r} is not written "
                             f"(the program computes {want!r})")
    return DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling={k: rope[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")},
        first_k_dense_replace=int(cfg["first_k_dense_replace"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        n_routed_experts=int(cfg["n_routed_experts"]),
        n_routed_experts_published=int(cfg["n_routed_experts_published"]),
        expert_offset=int(cfg["expert_offset"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        n_shared_experts=int(cfg["n_shared_experts"]),
        n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position_embeddings=int(cfg["max_position_embeddings"])))


def partition(name, axis):
    raise NotImplementedError(
        "deepseek_v2 is served on one chip: experts over chips with their "
        "exchange are not written (ROADMAP Queue 2)")
