"""The grouped-query paged decode kernel's share of its roofline at 2 K/V heads of 128 in
the two attention layers alone (``rooflines/nemotron_paged_gqa_decode.py``; memory
bandwidth is the bound), over ``fused_paged_decode_attention_gqa`` time."""

from benchmark.trace import nemotron

read = nemotron.paged_gqa_roofline_pct
