"""Engine: rows that got a token per decode step, as a share of
``max_batch``, over the decode steps of the window."""


def read(record):
    w = record["window"]
    steps = [st for st in w["steps"] if st.rows and st.t1 <= w["end"]]
    if not steps:
        return None
    return 100.0 * sum(st.rows for st in steps) / (
        len(steps) * record["shapes"].max_batch)
