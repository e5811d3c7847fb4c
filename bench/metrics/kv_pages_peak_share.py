"""KV arena: the most pages live at once (parked prefix donors
included), read after every step, as a share of the page pool."""


def read(record):
    return 100.0 * record["window"]["kv_peak_pages"] / record[
        "shapes"].pool_pages
