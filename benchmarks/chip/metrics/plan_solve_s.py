"""Seconds the program spent in its schedule solvers
(``core/autotune.py``) up to the end of the window: ``total_s`` of its
span ``autotune.plan``, which times each outermost solver entry, cache
hit or miss.  None where the program has no such span."""

from scopes import PLAN_SPAN, span_total_s


def read(reading):
    return span_total_s(reading, PLAN_SPAN)
