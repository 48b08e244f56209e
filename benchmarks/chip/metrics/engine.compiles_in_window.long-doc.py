"""Programs compiled inside the window: JAX's backend compiles less its
loads from the persistent cache, as the harness counts them
(``compiles_in_window``).  0 when every chunk bucket was warmed up."""
from chipbench import harness


def read(run):
    events = getattr(run, "compile_events", None)
    if events is None:
        return None
    inside = [e[0] for e in events if run.window[0] <= e[2] <= run.window[1]]
    return float(inside.count(harness.BACKEND_COMPILE)
                 - inside.count(harness.CACHE_HIT))
