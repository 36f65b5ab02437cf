"""Breaks ``reply_attachment`` (resident on the caller's chip): the channel
does not say which chip its caller lives on, so the reply lands on the
server's neighbour (the program's default) and not on the caller's chip."""
GUARANTEE = "reply_attachment"


def channel_options(options):
    return dict(options, ici_local_device=None)
