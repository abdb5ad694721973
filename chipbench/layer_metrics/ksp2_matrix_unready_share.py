"""KSP2 engine: the share of the window's incremental syncs that began
while the previous window's matrix solve was still running
(``decision.ksp2_matrix_unready`` over
``decision.ksp2_incremental_syncs``), in percent. A one-chip engine
solves the all-pairs matrix it keeps BEHIND the window and waits only
for the rows it reads; a sync that finds the matrix not ready queues
its rows solve behind it on the device and so waits for it after all.
0 where every sync found the matrix landed. Nothing where the engine
never synced, or from a program that does not keep the counter (one
that waits for the matrix in every sync)."""


def read(record):
    syncs = record.counter("decision.ksp2_incremental_syncs")
    if not syncs or "decision.ksp2_matrix_unready" not in record.counters:
        return None
    return 100.0 * record.counter("decision.ksp2_matrix_unready") / syncs
