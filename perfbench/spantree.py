"""Sums of a count over trees of the program's recorded spans."""


def tree_total(spans, roots, key):
    """The sum of count ``key`` over ``roots`` and every span below them
    in ``spans``."""
    children = {}
    for s in spans:
        children.setdefault(s.parent, []).append(s)
    total, todo = 0, list(roots)
    while todo:
        s = todo.pop()
        total += s.counts.get(key, 0)
        todo.extend(children.get(s.id, ()))
    return total
