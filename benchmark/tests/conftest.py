"""Tests of the benchmark's own code: ``python -m pytest benchmark/tests`` (CPU)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOY = dict(
    vocab_size=97, max_seq_len=64, max_latents=16, num_channels=32, num_heads=2,
    num_self_attention_layers=3, num_self_attention_rotary_layers=1,
    self_attention_widening_factor=4, cross_attention_widening_factor=4,
    cross_attention_dropout=0.0, abs_pos_emb=False, output_norm=True, output_bias=True,
    init_scale=0.1,
)
