"""Percent of the window the scenario service spent staging its launches:
the sum of ``ScenarioReport.stage_s`` (one per launch) over the window."""


def read(ctx):
    if not all("stage_s" in c for c in ctx.calls):
        return None
    return 100.0 * sum(c["stage_s"] for c in ctx.calls) / ctx.window_s
