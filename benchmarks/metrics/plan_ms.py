"""Front end: mean milliseconds of the program's ``plan`` spans
(``exec/runner.py``: parse is before it, the plan cache inside it) per
query of the window."""


def read(run):
    spans = [s for s in run["spans"] if s["name"] == "plan"]
    if not spans:
        return None
    return 1e3 * sum(s["end"] - s["start"] for s in spans) / len(spans)
