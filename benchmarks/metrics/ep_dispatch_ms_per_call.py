from benchmarks.harness import program_spans


def read(view, reader):
    return program_spans.per_call_ms(view, reader["span"])
