"""The grouped-query paged decode kernel's share of its roofline (memory bandwidth is the
bound): ``rooflines/paged_gqa_decode.py`` over ``fused_paged_decode_attention_gqa`` time."""

from benchmark.trace import recurrent

read = recurrent.paged_gqa_roofline_pct
