"""Peak device memory in use after the window
(``memory_stats()["peak_bytes_in_use"]``), in GiB."""


def read(r):
    if r.get("peak_bytes") is None:
        return None
    return r["peak_bytes"] / 2**30
