"""Share of the rows dispatched in the traced slice that are padding.

Each cycle pads its batch up to a bucket before encode. The bucket cache
counts its executable calls per bucket, so the dispatched rows are the sum
of bucket times calls, and the padding is that less the admitted rows.
(The cache's own ``padded_rows`` counter sees only padding it adds itself,
and the service pads before it.)"""


def padded_rows(bucket_calls: dict, admitted: int) -> int:
    return sum(int(b) * int(n) for b, n in bucket_calls.items()) - admitted


def read(run):
    c = run.slice_counters
    if not c or c["admitted"] <= 0:
        return None
    pad = padded_rows(c["bucket_calls"], c["admitted"])
    return 100.0 * pad / (c["admitted"] + pad)
