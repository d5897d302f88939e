"""Share of the window's slot-steps in which the slot was free and the slot
lane's queue was empty (%): ``slot_steps_starved``, open to close. The rest
of what ``slot.occupancy`` leaves is free with requests waiting."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.steps_share_pct(ctx, "slot_steps_starved")
