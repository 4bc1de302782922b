"""Share of the tick programs' device busy time that the state-update kernel
(``ssm_decode_update``) takes."""

from benchmark.trace import recurrent

read = recurrent.ssm_update_share_pct
