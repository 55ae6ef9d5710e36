"""converge_iters: power iterations per window epoch, as the program's
``ConvergenceResult`` counts them."""


def read(run):
    epochs = run["epochs"]
    return sum(e.iterations for e in epochs) / len(epochs)
