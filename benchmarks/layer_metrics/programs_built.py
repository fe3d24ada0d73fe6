"""Compile + dispatch (exec/base.process_jit, obs/compileprof.py,
plugin.init_compilation_cache): programs the ``CompileObservatory`` saw
built from process start to the window's start (each is a compile or a
load from the persistent cache)."""


def read(run):
    return float(run.builds_at_window)
