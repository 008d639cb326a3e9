"""The append-only rule of the benchmark's files, in one place.

Later PRs add cells, rows and files and edit none that is there, so a
test may hold a list only as the START of what the manifest lists, and a
set only as a PART of what is found: a snapshot of the whole breaks with
the next appended row (eleven such tests stood red from PR 29 to PR 33).
"""


def appended_only(manifest_list, file_list) -> bool:
    """The manifest's list is the file's list, then what was appended."""
    return list(manifest_list[:len(file_list)]) == list(file_list)


def rows_at_least(found, named) -> bool:
    """Every name that is held is found; more may have been added."""
    return set(named) <= set(found)


def in_order(listed, ours) -> bool:
    """`ours` stand in `listed` in their own order, wherever later rows
    stand among or behind them."""
    return [n for n in listed if n in set(ours)] == list(ours)
