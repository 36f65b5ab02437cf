"""Two counts the closed table does not read: the bytes the servers' stream
handlers have consumed (the program's own per-stream count, over the streams
that are live) and the writes a ``FixtureStream`` handler could not make."""
KEYS = ("fixture_stream_consumed_bytes", "fixture_stream_write_failures")


def snapshot(servers):
    from brpc_tpu.rpc.stream import live_streams
    consumed = sum(s._local_consumed for s in live_streams()
                   if not s.is_client)
    failures = sum(getattr(svc, "write_failures", 0)
                   for server in servers
                   for svc in server.services().values())
    return {"fixture_stream_consumed_bytes": consumed,
            "fixture_stream_write_failures": failures}
