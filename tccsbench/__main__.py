import sys

from tccsbench.run import main

sys.exit(main())
