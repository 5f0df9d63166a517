"""Requests per dynamic batch, from ``CNNServer.stats()``."""


def read(rec):
    if rec["loop"] != "open" or not rec["server_stats"]["batches"]:
        return None
    return rec["server_stats"]["mean_batch"]
