"""``unpack_s_per_slab``: host seconds from a slab's device result to
its volume in natural order, s.

Mean over the window's slabs of the ``recon/unpack`` spans, which follow
the fenced ``recon/solve``: the copy back, the unpack from the stored
order, the divide by the slab's scale and the finiteness check.
"""


def read(record):
    spans = record["spans"]
    if not spans:
        return None
    done = [s for s in spans if s["kind"] == "span"]
    slabs = [s for s in done if s["name"] == "stream/slab"]
    unpack = [s["t1"] - s["t0"] for s in done
              if s["name"] == "recon/unpack"]
    if not slabs or not unpack:
        return None
    return sum(unpack) / len(slabs)
