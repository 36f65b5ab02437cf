from benchmarks.harness import span_cpu


def read(view, reader):
    return span_cpu.cpu_per_call_ms(view, reader["span"])
