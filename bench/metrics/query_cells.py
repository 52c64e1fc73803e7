"""query_cells (cells/query): the search's work per query, as the program
counts it: ``SearchStats.cells_evaluated + probes``, over the queries of
the traced window."""


def read(facts: dict, trace):
    counts = facts.get("query_cells")
    if not counts:
        return None
    return sum(counts) / len(counts)
