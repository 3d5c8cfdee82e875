"""Mean live rows per decode dispatch in the window."""


def read(run):
    rows = run["work"]["decode_rows"]
    return sum(rows) / len(rows) if rows else None
