"""Traffic mixes: ``<name>.json`` holds a mix's parameters and names its
generator ``kind``; ``<kind>.py`` turns parameters and a seed into a
``Source`` (see ``bench.client``)."""
