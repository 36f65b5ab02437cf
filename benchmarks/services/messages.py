"""The request and reply messages of the benchmark's services."""
from examples.example_echo_pb2 import EchoRequest as Request    # noqa: F401
from examples.example_echo_pb2 import EchoResponse as Response  # noqa: F401
