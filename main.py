#!/usr/bin/env python
"""handyrl_tpu CLI: train / train-server / worker / eval / eval-server /
eval-client, mirroring the reference's six modes (main.py:19-38)."""

import sys

from handyrl_tpu.config import load_config

USAGE = """usage: python main.py MODE [args]
modes:
  --train, -t          stand-alone training on this host
  --train-server, -ts  training server awaiting remote workers
  --worker, -w         worker host feeding a training server [num_parallel]
  --eval, -e           evaluate MODEL_PATH[:OPPONENT] [NUM_GAMES [NUM_PROC]]
  --eval-server, -es   network battle server [NUM_GAMES [NUM_PROC]]
  --eval-client, -ec   network battle client MODEL_PATH [HOST]
  --serve, -sv         standalone model-serving tier (registry-versioned
                       inference service; SIGTERM drains and exits 75)
  --serve-fleet, -sf   replicated serving fleet: resolver/router +
                       serving.fleet.replicas managed replicas (SLO-driven
                       autoscaling, zero-loss failover, rolling promotes)
  --gateway, -gw       match gateway over a serving fleet: server-held
                       game sessions (open/play/close), drain handoff +
                       journal-replay reconstruction, outcomes -> RatingBook
  --status             render a live /statusz health view [HOST:PORT]
                       (active alerts, fleet states, progress, recorder)
"""


def main():
    from handyrl_tpu import setup_compile_cache
    setup_compile_cache()

    args = load_config('config.yaml')
    print(args)

    if len(sys.argv) < 2:
        print(USAGE)
        sys.exit(1)

    mode = sys.argv[1]
    rest = sys.argv[2:]

    if mode in ('--train', '-t'):
        from handyrl_tpu.train import train_main
        train_main(args)
    elif mode in ('--train-server', '-ts'):
        from handyrl_tpu.train import train_server_main
        train_server_main(args)
    elif mode in ('--worker', '-w'):
        from handyrl_tpu.worker import worker_main
        worker_main(args, rest)
    elif mode in ('--eval', '-e'):
        from handyrl_tpu.evaluation import eval_main
        eval_main(args, rest)
    elif mode in ('--eval-server', '-es'):
        from handyrl_tpu.evaluation import eval_server_main
        eval_server_main(args, rest)
    elif mode in ('--eval-client', '-ec'):
        from handyrl_tpu.evaluation import eval_client_main
        eval_client_main(args, rest)
    elif mode in ('--serve', '-sv'):
        from handyrl_tpu.serving.service import serve_main
        serve_main(args, rest)
    elif mode in ('--serve-fleet', '-sf'):
        from handyrl_tpu.serving.fleet import resolver_main
        resolver_main(args, rest)
    elif mode in ('--gateway', '-gw'):
        from handyrl_tpu.serving.gateway import gateway_main
        gateway_main(args, rest)
    elif mode == '--status':
        from handyrl_tpu.telemetry import status_main
        status_main(args.get('train_args'), rest)
    else:
        print('Not found mode %s.' % mode)
        print(USAGE)


if __name__ == '__main__':
    main()
