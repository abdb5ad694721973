"""Rebuild, host side: routes per rebuild that the full-db diff sent to
the field-by-field comparison, because the new db's entry was not the
installed object itself (what the route build re-derived, and adds):
median of the ``compared`` attribute over the window's
``decision.route_diff`` spans. The rest of the table was settled by
identity; a program whose diff does not say (no such attribute) gives
nothing."""
from chipbench import stats


def read(record):
    compared = [
        s.attrs["compared"] for s in record.spans
        if s.name == "decision.route_diff" and "compared" in s.attrs
    ]
    return stats.median(compared) if compared else None
