"""Device syncs the executor issues per inference
(`ExecutionReport.sync_points` of the window's last run)."""


def read(ctx):
    return ctx["raw"].get("sync_points")
