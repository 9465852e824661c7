"""``stream_wait_s_per_slab``: seconds a slab waits for its load and
upload, s.

Mean over the window's slabs of the ``stream/wait`` spans: the main
thread blocked on the prefetcher, which is the part of the disk read and
the host-to-device copy that the previous slab's solve did not hide.
"""


def read(record):
    spans = record["spans"]
    if not spans:
        return None
    done = [s for s in spans if s["kind"] == "span"]
    slabs = [s for s in done if s["name"] == "stream/slab"]
    waits = [s["t1"] - s["t0"] for s in done if s["name"] == "stream/wait"]
    if not slabs or not waits:
        return None
    return sum(waits) / len(slabs)
