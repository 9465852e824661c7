"""``host_s_per_slab``: host seconds on a slab's critical path.

Mean over the window's slabs of the ``stream/slab`` span less the
``recon/solve`` span inside it: waiting for the prefetch, the upload
when it is not overlapped, unpacking, the finiteness check and the
volume write.
"""


def read(record):
    spans = record["spans"]
    if not spans:
        return None
    done = [s for s in spans if s["kind"] == "span"]
    slabs = [s for s in done if s["name"] == "stream/slab"]
    solves = [s for s in done if s["name"] == "recon/solve"]
    if not slabs:
        return None
    host = []
    for sl in slabs:
        inner = sum(s["t1"] - s["t0"] for s in solves
                    if s["thread_id"] == sl["thread_id"]
                    and sl["t0"] <= s["t0"] and s["t1"] <= sl["t1"])
        host.append(sl["t1"] - sl["t0"] - inner)
    return sum(host) / len(host)
