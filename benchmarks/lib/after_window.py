"""What a family wants done after the window and before the per-layer
readers read the program's counters: callables of no argument, run in
order by the reader that needs them (the drivers hand the readers no
family). Off the dispatch path and outside every clock."""

HOOKS: list = []
