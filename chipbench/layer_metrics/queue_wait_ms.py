"""Ingest: median of the program's ``decision.queue_wait`` span, the
``kvstore.publish`` instant -> Decision's thread picks the publication
up (the ``ReplicateQueue`` hop and the wake-up; ``ingest_ms`` minus this
is ``process_publication``)."""


def read(record):
    return record.span_median("decision.queue_wait")
