"""Device time of the collectives inside the fused serve step, per
execution, in ms: on the first device, the union of the collective ops
that fall inside ``jit_serve_step`` executions starting in the traced
window, over those executions. Collectives are the ops ``trace.COLLECTIVE``
names, and the all-reduces that JAX names after a ``shard_map``'s
``psum`` or ``pmax`` (flash-decoding's merge: ``psum.17``). A serve step
with no collective reads 0; nothing to read without a trace holding one."""
import re

from chipbench import trace as T
from chipbench.metrics._programs import SERVE_STEP

SHARD_MAP_REDUCTION = re.compile(r"^%?p(?:sum|max|min)(?:\.\d+)?(?:\s|$)")


def is_collective(name: str) -> bool:
    return bool(T.COLLECTIVE.search(name) or SHARD_MAP_REDUCTION.match(name))


def read(obs):
    if obs.trace is None or not obs.trace.ops:
        return None
    n, _ = T.module_seconds(obs.trace, SERVE_STEP)
    if not n:
        return None
    lo, hi = obs.trace.window()
    plane = T.planes(obs.trace)[0]
    rx = re.compile(SERVE_STEP)
    coll = [e for e in obs.trace.ops.get(plane, []) if is_collective(e[0])]
    ns = sum(T.length(T.merge(T.clip(coll, s, s + d)))
             for name, s, d in obs.trace.modules.get(plane, [])
             if rx.search(name) and lo <= s < hi)
    return ns * 1e-6 / n
