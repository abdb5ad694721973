"""Debounce: the share of windows whose work outlasted the policy wait
(``slack_ms`` of ``decision.debounce`` below 0: the last callback before
the fire ended after the deadline), in percent."""
from chipbench import spantail


def read(record):
    return spantail.overrun_share(spantail.window_terms(record, "slack_ms"))
