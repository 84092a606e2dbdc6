"""A cell small enough for the CPU, for the benchmark's tests."""


def small_spec(qk_norm=False, layers=2):
    """A dense configuration file at a size the CPU runs in seconds."""
    return {
        "name": "small", "source": "test", "family": "dense",
        "config": {"hidden_size": 128, "intermediate_size": 256,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 32, "num_hidden_layers": layers,
                   "vocab_size": 512, "hidden_act": "silu",
                   "qk_norm": qk_norm, "tie_word_embeddings": True,
                   "rope_theta": 10000.0, "rms_norm_eps": 1e-6},
        "reduced": [], "dtype": "bfloat16",
        "serving": {"n_slots": 4, "page_size": 16,
                    "max_prefill_per_step": 2},
        "check": {"max_logit_gap": 0.25},
    }


def small_traffic(loop="open"):
    t = {"name": "small", "loop": loop, "block": 4,
         "prompt_tokens": {"values": [16, 48], "p": [0.5, 0.5]},
         "output_tokens": {"values": [4, 12], "p": [0.75, 0.25]}}
    if loop == "open":
        t.update(rate_per_s=6.0, arrivals="stratified")
    else:
        t.update(clients=6, think_s=0.0)
    return t

