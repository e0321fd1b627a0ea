"""Share of the traced window in which the chip ran no operation, %:
1 - (union of the device's op intervals) / window."""


def read(view):
    idle = view.idle_share()
    return None if idle is None else idle * 100.0
