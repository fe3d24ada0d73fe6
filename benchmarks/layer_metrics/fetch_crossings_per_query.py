"""D2H fetch (columnar/fetch.py): device-to-host crossings per query, the
delta of the program's counter ``tpu_fetch_crossings_total`` over the
window by the queries completed in it."""


def read(run):
    if not run.times_ms:
        return None
    return (run.crossings_at_end - run.crossings_at_window) / \
        len(run.times_ms)
