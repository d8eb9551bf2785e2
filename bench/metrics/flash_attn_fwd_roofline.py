"""Share of its roofline that the ``flash_attn_fwd`` kernel reached in the
traced window: the least time a call needs (bench/counts.py) over the
time a call took, in percent."""
from bench.counts import roofline_share


def read(obs):
    return roofline_share(obs, "flash_attn_fwd")
