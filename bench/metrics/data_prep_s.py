"""data_prep_s: host seconds the program spent making the city and its
training windows (``data.make_dataset`` + ``data.build_windows``), from
the totals of its spans recorded over the run (``program_spans``)."""

SPANS = ("data.make_dataset", "data.build_windows")


def read(record):
    spans = record.get("program_spans") or {}
    hits = [spans[n]["seconds"] for n in SPANS if n in spans]
    return sum(hits) if hits else None
