"""lanes_useful_pct (service), read as ``lanes_useful_pct.serve`` in the
service cell: the lanes asked over the lanes replayed, summed over the
window's replays, in percent, from the ``lanes`` and ``padded_to``
attributes of the program's ``serve.replay`` spans
(``bench/program_spans.py``). The rest are padding: repeats of a lane that
the replay runs in full to fill whole scenario chunks."""
from bench import program_spans


def read(run):
    replays = program_spans.named(program_spans.window_records(),
                                  "serve.replay")
    replayed = sum(r.attrs["padded_to"] for r in replays)
    if not replayed:
        return None
    return 100.0 * sum(r.attrs["lanes"] for r in replays) / replayed
