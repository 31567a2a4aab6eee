"""setup_s: process start to the window's start (host clock): traffic,
KitNET fit by the reference, engine build, compilation or cache loads,
warm-up of every lane count."""


def read(m):
    return m["setup_s"]
