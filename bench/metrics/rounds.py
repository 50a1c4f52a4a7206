"""rounds (executor), read as ``rounds.<part>`` in the sweep cells: the
largest number of Algorithm-2 rounds over the sweep's lanes, from one
``execute_sweep`` call with the cell's plan after the window. A count:
each round is one pass of the round loop."""


def read(run):
    record = run["obs"].get("round_record")
    if record is None:
        return None
    return float(record["num_rounds"].max())
