"""Host planning, transport choice: the share of decoded pages whose
values the host assembled (``pages_host_values``) or decoded after a
degrade (``pages_degraded``), of all pages decoded."""


def read(ctx):
    st = ctx.stats
    if not st.pages:
        return None
    return 100.0 * (st.pages_host_values + st.pages_degraded) / st.pages
