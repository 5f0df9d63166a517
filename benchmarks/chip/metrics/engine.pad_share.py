"""Padded rows over bucket rows of the served batches, in percent, from
each ``ImageResult``'s ``batch_size`` and ``bucket``: a batch of ``n``
gives ``n`` results, so each result carries ``1/n`` of its batch."""


def read(rec):
    if rec["loop"] != "open":
        return None
    pad = rows = 0.0
    for res in rec["results"].values():
        if type(res).__name__ == "ImageResult":
            pad += (res.bucket - res.batch_size) / res.batch_size
            rows += res.bucket / res.batch_size
    return 100.0 * pad / rows if rows else None
