"""A configuration and mixes small enough for CPU tests, of the same
architecture and recipe as the benchmark's own."""
import json
from pathlib import Path

VOCAB = 4096
CONFIG = Path(__file__).resolve().parents[1] / "configs" / "qwen1.5-0.5b.json"


def small_config():
    c = json.loads(CONFIG.read_text())
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=VOCAB)
    c["program"] = {"arch": "qwen1.5-0.5b", "overrides": {
        "name": "qwen1.5-bench-small", "d_model": 64, "d_ff": 128, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "n_layers": 2, "pattern_reps": 2,
        "vocab_size": VOCAB, "rope_theta": c["rope_theta"]}}
    return c


def small_mix(mix):
    return dict(mix, prompt_len=16, prompt_pool=4)
