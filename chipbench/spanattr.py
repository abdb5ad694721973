"""An attribute of the program's spans, for the readers whose metric is
what a span says about itself rather than how long it took."""

from __future__ import annotations

from typing import Optional

from chipbench import stats


def median(record, span: str, attr: str) -> Optional[float]:
    """Median of attribute ``attr`` over the window's spans ``span``
    that carry it; ``None`` from a program whose span does not say."""
    values = [
        s.attrs[attr] for s in record.spans
        if s.name == span and attr in s.attrs
    ]
    return stats.median(values) if values else None
