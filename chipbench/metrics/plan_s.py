"""Host seconds of the planner's ``plan.build`` span (repro.obs) in
set-up: statistics, the modeled election and any measured trial.  Later
solves hit the plan cache and build nothing."""


def read(r):
    durs = [s["dur"] for s in r.setup_spans if s["name"] == "plan.build"]
    return sum(durs) if durs else None
