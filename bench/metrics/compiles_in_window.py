"""Host control plane: executables JAX built or loaded inside the window
(the pow2 bucket contract says none)."""


def read(rec):
    return float(rec.compiles_in_window)
