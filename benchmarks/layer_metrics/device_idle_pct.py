"""device: 1 - busy/window over the profiler's window."""


def read(run: dict):
    p = run.get("profile")
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"]) if p and p["window_s"] > 0 else None
