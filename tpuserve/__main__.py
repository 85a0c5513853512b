import sys

from tpuserve.cli import main

# Guarded: multiprocessing's spawn start method re-imports the parent's
# __main__ in every child (router workers);
# an unguarded entry would re-run the whole CLI inside each of them.
if __name__ == "__main__":
    sys.exit(main())
