"""Per cent of the prefill chunks' tokens that are bucket padding: over
the window's chunks, (bucket - length) over bucket, each chunk's bucket
as ``repro.serving.engine.bucket_chunk`` sets it (the ``bucket`` and
``pad`` arguments of the program's ``engine.prefill_chunk`` spans)."""


def read(run):
    from repro.serving.engine import bucket_chunk
    size = run.cell.config["engine"]["chunk_size"]
    lengths = [c[2] for it in getattr(run, "iterations", ()) for c in it.chunks]
    buckets = sum(bucket_chunk(n, size) for n in lengths)
    if not buckets:
        return None
    return 100.0 * (buckets - sum(lengths)) / buckets
