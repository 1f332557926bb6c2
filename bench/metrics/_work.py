"""The work of each engine step in the traced slice, as a lower bound: the
step's StepRecord and its planned rows, with rows the step did not run left
out (``bench.arch`` says why each count is a lower bound)."""


def traced_steps(run):
    """[(StepRecord, {"prefill": [(start, n)], "decode": [context]})] of the
    steps whose spans lie wholly in the trace."""
    if not run.trace:
        return []
    records = {(r, s.step): s for r, steps in run.steps.items() for s in steps}
    out = []
    for key in run.trace["steps"]:
        rec, plan = records.get(tuple(key)), run.plans.get(tuple(key))
        if rec is None or plan is None:
            continue
        prefill = plan["prefill"] if rec.prefill_rows == len(plan["prefill"]) else []
        decode = sorted(plan["decode"])[:rec.decode_rows]
        out.append((rec, {"prefill": prefill, "decode": decode}))
    return out
