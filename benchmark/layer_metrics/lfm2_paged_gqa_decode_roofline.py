"""The grouped-query paged decode kernel's share of its roofline at 8 K/V heads of 64 in
the attention layers alone (``rooflines/lfm2_paged_gqa_decode.py``; memory bandwidth is
the bound), over ``fused_paged_decode_attention_gqa`` time."""

from benchmark.trace import experts

read = experts.paged_gqa_roofline_pct
