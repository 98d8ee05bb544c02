"""Model FLOP utilisation of the whole serving loop, in percent: FLOPs
of the prompts that got their first token and of the decoded tokens
received while the trace ran, over its seconds times chips times the bf16
peak."""
from chipbench import flops as F


def read(obs):
    if obs.trace is None or obs.peaks is None:
        return None
    lo, hi = obs.trace_window
    total = 0
    for t in obs.tracks:
        p = len(t.req.prompt)
        for k, s in enumerate(t.tokens):
            if lo <= s < hi:
                total += (F.prefill_flops(obs.cfg, p) if k == 0 else
                          F.decode_token_flops(obs.cfg, p + k))
    return 100.0 * total / ((hi - lo) * obs.chips
                            * obs.peaks["bf16_flops_per_s"])
