"""The engine's StepRecords of the traced slice: those of the steps whose
spans lie wholly in the trace."""


def traced_records(run, field: str):
    """The traced steps' StepRecords that carry ``field`` (a program that
    predates the field leaves it out, and its readers then read nothing)."""
    if not run.trace:
        return []
    records = {(r, s.step): s for r, steps in run.steps.items() for s in steps}
    return [rec for key in run.trace["steps"]
            if (rec := records.get(tuple(key))) is not None and hasattr(rec, field)]
