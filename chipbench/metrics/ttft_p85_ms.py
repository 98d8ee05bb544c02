"""85th percentile of time to first token (see ``_ttft``): the highest
round percentile with ten requests beyond it in the chat window (about
82 requests due in 51 s at 1.6 req/s)."""
from chipbench.metrics._ttft import ttfts_ms
from chipbench.stats import pct


def read(obs):
    return pct(ttfts_ms(obs), 85)
