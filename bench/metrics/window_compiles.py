"""Compilation: XLA backend compiles inside the measured window (a program
served from the persistent compile cache does not count). Warm cells read 0."""


def read(rec):
    return rec["window_compiles"]
