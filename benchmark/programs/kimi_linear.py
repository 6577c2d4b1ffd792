"""The program a configuration names with ``"program": "kimi_linear"``:
``paddle_tpu/models/kimi_linear.py`` (Kimi Delta Attention layers with a
recurrent state a slot beside latent attention over a paged latent pool,
a dense first layer, then sparse experts of which this chip holds a
share), at whatever sizes the configuration states. Serving only: the
model has no backward. ``programs/llama.py``'s docstring is the contract."""


def build(cfg):
    from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM
    lin = cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    for key, want in (("moe_router_activation_func", "sigmoid"),
                      ("num_expert_group", 1), ("topk_group", 1),
                      ("mla_use_nope", True), ("q_lora_rank", None),
                      ("moe_layer_freq", 1), ("hidden_act", "silu")):
        if cfg[key] != want:
            raise ValueError(f"kimi_linear: {key}={cfg[key]!r} is not "
                             f"written (the program computes {want!r})")
    return KimiLinearForCausalLM(KimiLinearConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=layers,
        num_attention_heads=int(cfg["num_attention_heads"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        kda_layers=tuple(i for i in lin["kda_layers"] if i <= layers),
        linear_num_heads=int(lin["num_heads"]),
        linear_head_dim=int(lin["head_dim"]),
        short_conv_kernel_size=int(lin["short_conv_kernel_size"]),
        gate_low_rank=int(cfg["gate_low_rank"]),
        first_k_dense_replace=int(cfg["first_k_dense_replace"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=int(cfg["num_experts"]),
        num_experts_published=int(cfg["num_experts_published"]),
        expert_offset=int(cfg["expert_offset"]),
        num_experts_per_token=int(cfg["num_experts_per_token"]),
        num_shared_experts=int(cfg["num_shared_experts"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        moe_renormalize=bool(cfg["moe_renormalize"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_position_embeddings=int(cfg["model_max_length"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"])))


def partition(name, axis):
    raise NotImplementedError(
        "kimi_linear is served on one chip: experts over chips with their "
        "exchange are not written (ROADMAP Queue 2)")
