"""Front end: the client's seconds less the program's ``query`` span,
mean per query, in milliseconds: HTTP, session handling, paging and row
encoding, on both sides of the socket."""


def read(run):
    spans = [s for s in run["spans"] if s["name"] == "query"]
    if not spans or len(spans) != len(run["seconds"]):
        return None
    inside = sum(s["end"] - s["start"] for s in spans)
    return 1e3 * (sum(run["seconds"]) - inside) / len(spans)
