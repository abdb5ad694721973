"""Route programming: median of the program's ``decision.emit`` span
(apply the delta to Decision's own route db, stamp, ready the update
for the queue)."""


def read(record):
    return record.span_median("decision.emit")
