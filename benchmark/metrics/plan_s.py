"""plan_s: seconds per window epoch in the program's ``plan`` spans
(``trust/backend.py::_resolve_plan``: reuse check, delta or rebuild,
all host numpy).  Nothing to read where no epoch opened one."""


def read(run):
    epochs = run["epochs"]
    if not any(e.plan != "none" for e in epochs):
        return None
    return sum(e.plan_s for e in epochs) / len(epochs)
