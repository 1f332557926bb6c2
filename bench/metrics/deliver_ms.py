"""Replica: mean over the traced steps of the time the serving thread took
to hand the step's token events to their requests (the program's
``replica.deliver`` span, ``StepRecord.deliver_s``), milliseconds."""
from bench.metrics._records import traced_records


def read(run):
    records = traced_records(run, "deliver_s")
    return 1e3 * sum(r.deliver_s for r in records) / len(records) if records else None
