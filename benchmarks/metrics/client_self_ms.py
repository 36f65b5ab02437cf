from benchmarks.harness import program_spans


def read(view, reader):
    return program_spans.self_ms(view, reader["span"], reader["less"])
