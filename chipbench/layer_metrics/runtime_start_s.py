"""Seconds from the start of the process to the devices being attached:
the interpreter, the import of JAX and the TPU runtime's own start-up,
which ``setup_s`` leaves out (PERF.md, PR 23)."""


def read(obs):
    return obs.get("runtime_start_s")
