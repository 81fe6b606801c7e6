"""Seconds from process start to the window's start: imports, weights
made on the device, compilation or the compile cache, warm-up, and the
set-up steps the traffic asks for."""


def read(w):
    return w.setup_s
