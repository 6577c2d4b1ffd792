"""The program a configuration names with ``"program": "ouro"``:
``paddle_tpu/models/ouro.py`` (one stack of sandwich-norm blocks run
``total_ut_steps`` times a token with the same weights, each run of a layer
with keys and values of its own in a paged pool, the final norm and an exit
gate at the end of every run), at whatever sizes the configuration states.
Serving only: the model has no backward. ``programs/llama.py``'s docstring
is the contract."""


def build(cfg):
    from paddle_tpu.models import OuroConfig, OuroForCausalLM
    # what the program does not compute is refused by name, not guessed
    for key, got, want in (
            ("hidden_act", cfg["hidden_act"], "silu"),
            ("rope_scaling", cfg["rope_scaling"], None),
            ("sliding_window", cfg["sliding_window"], None),
            ("use_sliding_window", cfg["use_sliding_window"], False),
            ("layer_types", sorted(set(cfg["layer_types"])),
             ["full_attention"])):
        if got != want:
            raise ValueError(f"ouro: {key}={got!r} is not written (the "
                             f"program computes {want!r})")
    return OuroForCausalLM(OuroConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        total_ut_steps=int(cfg["total_ut_steps"]),
        early_exit_threshold=float(cfg["early_exit_threshold"]),
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"])))


def partition(name, axis):
    raise NotImplementedError(
        "ouro is served on one chip: the looped pools over a mesh are not "
        "written (ROADMAP Queue 2)")
