"""Device trace: busy time under the program's scope ``opening`` (the level-wise opening waves and their one materialisation sort; 0 where ``tpu_wave_open_levels`` is 0),
per traced iteration."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms_per_iter(run, "opening")
