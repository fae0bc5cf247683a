"""Seconds from process start to the start of the traffic: imports,
weights made on the device, engine set-up, compile-cache reads and the
warm-up of every shape the traffic uses."""


def read(run):
    return run.setup_s
