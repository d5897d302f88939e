"""Share of the window's per-channel KDA log-decays (every real token's,
prefill and decode, over every KDA layer) below -5, the least one step of
the bounded gate can give (%): ``kda_decays_deep`` over ``kda_decays``,
open to close. A property of the seed's weights, not of serving: it says
how much of the recurrence the unbounded gate drives deeper than the
bounded gate can reach, i.e. how hard the cell's comparison tests the
chunk's deep-decay path. It is a guard on that comparison's power, not a
lever on throughput: a step's cost does not depend on it. Its entry names
``explanations_per_s`` under ``moves`` because every per-layer entry must
name one end-to-end metric and that is the one its cell reports besides
``setup_s``; its ``better: higher`` is informational (a higher share tests
more). Nothing to read for a program without the counters, or a model
whose gate is bounded."""

from benchmark.metrics import _lib


def read(ctx):
    if "kda_decays" not in ctx["marks"].get("open", {}):
        return None
    decays = _lib.delta(ctx, "open", "close", "kda_decays")
    deep = _lib.delta(ctx, "open", "close", "kda_decays_deep")
    return 100.0 * deep / decays if decays else None
