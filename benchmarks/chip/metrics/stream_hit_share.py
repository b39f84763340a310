"""Share of stream frames the sessions served from their cache
(sessions' hits over frames), in percent."""


def read(run):
    st = run.window.stream_stats
    if not st or not st.get("frames"):
        return None
    return 100.0 * st["hits"] / st["frames"]
