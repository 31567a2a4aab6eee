"""setup_s: process start to the window's start (host clock): traffic,
KitNET fit by the reference, engine build, compilation or cache loads,
warm-up of the lane counts the window batches."""


def read(m):
    return m["setup_s"]
