"""Print every reproduced row of the paper, one per line: ``name | text``."""
from repro.paper import rows

for row in rows():
    print(f"{row.name} | {row.derived}")
