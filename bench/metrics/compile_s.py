"""compile_s: seconds spent lowering and compiling the served executable,
or loading it from the persistent compile cache: the program's
`exec.compile` span summed over the run, which is set-up's (the window's
input shapes are compiled already)."""
import spans


def read(record):
    return spans.span_total_s("exec.compile")
