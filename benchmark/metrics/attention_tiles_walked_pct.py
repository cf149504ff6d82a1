"""The (q tile, k tile) pairs the windowed layers' kernels walk, as a
percentage of what the causal mask walks over the same tiles: the
program's gauge ``flash_attention_tiles{mask="sliding_window", which}``,
set where the tile tables are built.  A band of 512 keys in 8,192 at
256-wide tiles reads 93 of 528."""


def read(run):
    gauges = run["after"].get("gauges", {})
    key = 'flash_attention_tiles{mask="sliding_window",which="%s"}'
    walked, causal = gauges.get(key % "walked"), gauges.get(key % "causal")
    if not walked or not causal:
        return None
    return 100.0 * walked / causal
