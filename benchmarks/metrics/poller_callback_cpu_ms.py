from benchmarks.harness import span_cpu


def read(view, reader):
    return span_cpu.cpu_of_median_ms(view, reader["span"])
