"""The program a configuration names with ``"program": "brumby"``:
``paddle_tpu/models/brumby.py`` (a dense pre-norm decoder whose every layer
mixes tokens by power retention in its recurrent form: a float32 state a
(slot, layer) and no K/V pool at all), at whatever sizes the configuration
states. Serving only: the model has no backward. ``programs/llama.py``'s
docstring is the contract."""


def build(cfg):
    from paddle_tpu.models import BrumbyConfig, BrumbyForCausalLM
    # what the program does not compute is refused by name, not guessed
    for key, want in (("hidden_act", "silu"), ("rope_scaling", None),
                      ("sliding_window", None),
                      ("use_sliding_window", False),
                      ("attention_bias", False)):
        if cfg[key] != want:
            raise ValueError(f"brumby: {key}={cfg[key]!r} is not written "
                             f"(the program computes {want!r})")
    assumed = cfg.get("assumed", {})
    return BrumbyForCausalLM(BrumbyConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        retention_degree=int(assumed.get("retention_degree", 2)),
        retention_eps=float(assumed.get("retention_eps", 1e-6))))


def partition(name, axis):
    raise NotImplementedError(
        "brumby is served on one chip: a recurrent state sharded by head "
        "over a mesh is not written (ROADMAP Queue 2)")
