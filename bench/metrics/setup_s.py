"""setup_s (s, host clock): from the start of the process to the opening
of the measured window — imports, the device check, weights made from the
seed, engine construction and the warm-up that compiles (or loads from
the compile cache) every program the window runs."""


def read(rec):
    return rec.setup_s
