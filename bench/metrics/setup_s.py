"""Set-up: process start to the window's start (weights made on the device,
decode ladder and every prefill shape of the mix compiled or loaded,
system prompts registered)."""


def read(run):
    return run.setup_s
