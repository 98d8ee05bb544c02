"""Median time to first token (see ``_ttft``)."""
from chipbench.metrics._ttft import ttfts_ms
from chipbench.stats import pct


def read(obs):
    return pct(ttfts_ms(obs), 50)
