"""Record a small engine trace on the chip, with the program's spans and
the device's module events, and write its events for the tests.

    python3 benchmarks/chip/tools/span_probe.py <out.json>

Builds ``serving.Engine`` for minicpm3-4b at published widths with two
layers and four short cache rows, serves a warm-up burst, then serves
four requests under ``spans.Recorder``.  Prints every plane and line of
the raw trace with its event count and first event names, writes the
extracted events (``spans.load_events``' form, op names cut to
``trace.NAME_CHARS``) to ``<out.json>``, and prints ``spans.report``.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def serve(eng, prompts, new_tokens):
    from repro.serving.scheduler import Request
    for i, n in enumerate(prompts):
        eng.sched.add_request(Request(rid=i, arrival=0.0,
                                      prompt=[1 + (j % 97) for j in range(n)],
                                      max_new_tokens=new_tokens))
    while eng.sched.has_work():
        plan = eng.sched.schedule()
        eng.execute(plan)
        eng.sched.complete_iteration(plan, 0.0)


def main(out):
    import glob
    import os
    from jax.profiler import ProfileData
    from chipbench import cells, spans, trace
    from repro.serving.engine import Engine
    from repro.serving.scheduler import SchedulerConfig

    conf = cells.load_json(HERE / "configs" / "minicpm3-4b.json")
    cfg = cells.model_config(dict(conf, overrides={"n_layers": 2}))
    eng = Engine(cfg, max_seq=512, sched_config=SchedulerConfig(
        max_num_seqs=4, max_batch_tokens=128, chunk_size=64,
        prefix_caching=False))
    serve(eng, [100, 70, 40, 20], 3)          # every eager op runs once
    rec = spans.Recorder()
    rec.start()
    serve(eng, [90, 60, 30, 12], 3)
    rec.stop()
    path = glob.glob(os.path.join(rec.logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            ev = list(line.events)
            print(plane.name, "|", line.name, "|", len(ev),
                  [e.name[:60] for e in ev[:3]])
    events = rec.events()
    events["device"] = [[p, n[:trace.NAME_CHARS], s, d]
                        for p, n, s, d in events["device"]]
    with open(out, "w") as fh:
        json.dump(events, fh)
    print(json.dumps(spans.report(events, "serve")))


if __name__ == "__main__":
    main(sys.argv[1])
