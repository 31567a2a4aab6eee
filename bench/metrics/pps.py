"""pps: packets whose results were drained inside the window, per second
(host clock)."""


def read(m):
    return m["drained_packets"] / m["window_s"]
