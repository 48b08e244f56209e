"""Per cent of the HBM roofline the selective scan reaches: the bytes its
calls must move (``scan_bytes`` of the configuration's ``.py``, from each
call's rows and positions) over the device seconds of its ops in the
traced window (``scan_call`` names them: the Pallas kernel ``mamba_scan``
or the fusion around it) times the chip's HBM bytes per second
(``peaks.json``).  The scan is memory bound, so bytes are its roofline."""
from chipbench import cells


def read(run):
    events = getattr(run, "trace_events", None)
    model = run.cell.model
    if not events or not hasattr(model, "scan_call"):
        return None
    import jax
    peaks = cells.load_json(cells.BENCH_DIR / "peaks.json")["devices"]
    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        return None
    w0, w1 = next((s, s + d) for name, s, d in events["host"]
                  if name == "chipbench.window")
    nbytes = secs = 0.0
    end = None                      # of the last op counted: skip one inside
    for _, name, s, d in sorted(events["device"], key=lambda e: e[2]):
        call = model.scan_call(run.arch, name)
        if call and w0 <= s and s + d <= w1 and (end is None or s >= end):
            nbytes += model.scan_bytes(run.arch, *call)
            secs += d / 1e9
            end = s + d
    if secs <= 0:
        return None
    return 100.0 * nbytes / (secs * peaks[kind]["hbm_bytes_per_s"])
