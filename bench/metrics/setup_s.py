"""Seconds from the harness's start to the window's opening: building the
fleet, drawing the weights on the chips, training the policy's predictor,
loading or compiling every program and warming every shape."""


def read(w):
    return w.setup_s
