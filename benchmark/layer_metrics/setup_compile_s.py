"""Seconds of XLA backend compiles during set-up, from the program's
``compile_watch.GlobalCompileStats.summary()`` at the window's start."""


def read(obs):
    return obs["setup_compile"]["compile_secs"]
