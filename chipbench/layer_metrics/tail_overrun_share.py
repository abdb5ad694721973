"""The slowest decile's account: the share of its windows whose work
outlasted the policy wait (``slack_ms`` below 0), in percent: whether
the tail is the stage overrunning (``chipbench/spantail.py``). Nothing
under 200 traces or from a program whose windows do not say."""
from chipbench import spantail


def read(record):
    found = spantail.tail(record)
    return None if found is None else found.overrun_share
