"""The program a configuration names with ``"program": "dots3_note"``:
``paddle_tpu/models/dots3_note.py`` (latent attention over the positions a
learned indexer selects in the layers ``layer_types`` calls
``full_attention``, latent attention of a second geometry over a sliding
window in the others, a gate a head on both, a dense first layer, then
sparse experts of which this chip holds a share), at whatever sizes the
configuration states. Serving only: the model has no backward.
``programs/llama.py``'s docstring is the contract."""


def build(cfg):
    from paddle_tpu.models import Dots3NoteConfig, Dots3NoteForCausalLM
    layers = int(cfg["num_hidden_layers"])
    # what the program does not compute is refused by name, not guessed
    for key, want in (("apply_mla_qkv_lora_rescale", True),
                      ("attention_gate_type", "headwise"),
                      ("swa_attention_gate_type", "headwise"),
                      ("attention_bias", False),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True),
                      ("rope_scaling", None),
                      ("hidden_act", "silu"),
                      ("moe_layer_freq", 1),
                      ("n_shared_experts", 1),
                      ("tie_word_embeddings", False)):
        if cfg[key] != want:
            raise ValueError(f"dots3_note: {key}={cfg[key]!r} is not "
                             f"written (the program computes {want!r})")
    for key, heads in (("num_key_value_heads", "num_attention_heads"),
                       ("swa_num_key_value_heads",
                        "swa_num_attention_heads")):
        if cfg[key] != cfg[heads]:
            raise ValueError(f"dots3_note: {key}={cfg[key]!r} is not "
                             f"written (latent attention expands a key and "
                             f"a value a head: {heads}={cfg[heads]!r})")
    if len(cfg["layer_types"]) < layers:
        raise ValueError(f"dots3_note: layer_types names "
                         f"{len(cfg['layer_types'])} layers, the depth is "
                         f"{layers}")
    geometry = {key: int(cfg[key]) for pre in ("", "swa_") for key in (
        pre + "num_attention_heads", pre + "q_lora_rank",
        pre + "kv_lora_rank", pre + "qk_nope_head_dim",
        pre + "qk_rope_head_dim", pre + "v_head_dim")}
    return Dots3NoteForCausalLM(Dots3NoteConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=layers,
        layer_types=tuple(cfg["layer_types"][:layers]),
        rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        index_n_heads=int(cfg["index_n_heads"]),
        index_head_dim=int(cfg["index_head_dim"]),
        index_topk=int(cfg["index_topk"]),
        sliding_window_size=int(cfg["sliding_window_size"]),
        window_ring_rows=int(cfg["window_ring_rows"]),
        first_k_dense_replace=int(cfg["first_k_dense_replace"]),
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
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        **geometry))


def partition(name, axis):
    raise NotImplementedError(
        "dots3_note is served on one chip: experts over chips with their "
        "exchange are not written (ROADMAP Queue 2)")
