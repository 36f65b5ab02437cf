"""One file per RPC method the cells call, found by the method's name.

Each module gives ``SERVER_OPTIONS`` (fields of ``rpc.ServerOptions`` the
method's server is started with) and ``build(spans)``, which returns the
``rpc.Service`` to add.  ``spans`` is ``None`` in an untraced run; in a
traced one the handler stamps its boundaries into it under the request's
message, which is the call's key.
"""
