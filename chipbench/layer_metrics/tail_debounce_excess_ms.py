"""The slowest decile's account, debounce stage (the
``decision.debounce`` span): its median over the
slowest tenth of the window's traces minus its median over all of them
(``chipbench/spantail.py``). Nothing under 200 traces."""
from chipbench import spantail


def read(record):
    return spantail.excess_ms(record, "debounce")
