from benchmarks.harness import program_spans


def read(view, reader):
    return program_spans.median_ms(view, reader["span"])
