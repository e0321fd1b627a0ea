"""Share of the traced window in which the busiest of the cell's chips
ran no operation, %: 1 - (union of its op intervals) / window."""


def read(view):
    idle = view.idle_share()
    return None if idle is None else idle * 100.0
